"""The comparison that decides `correct`.

What is compared is what the timed path produced: the generation engine's
rollouts of the first wave (the same engine object, compiled decode
programs, 32 slots and full-length rollouts as the window), the
trainer's first three optimizer steps (the same trainer object the window
drives, through the pipeline's own call and feed), and the weights the
engine holds at the end against the trainer's.

Numbers, each against its limit in `workloads/<cell>.json`:

- engine_lp_gap: the widest gap between a sampled token's logprob as the
  engine carried it and the reference's logprob of that token;
- grad_gap: the worst leaf of the first gradient as Adam received it,
  |norm - reference norm| / max(reference norm, median leaf norm);
- update_gap: the same for the parameters' change over three steps, over
  the leaves whose reference gradient is at least a thousandth of the
  median leaf's;
- install_mismatch: versions the engine trails the trainer after the
  last publication has landed, plus leaves that differ bitwise (limit 0).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

import reference as R
import weights as W

UPDATE_LEAF_FLOOR = 1e-3


def install_mismatch(pipe, max_events: int = 200_000) -> int:
    """Stop the trainer, let the published weights land, and count the
    versions and leaves by which each engine differs from the trainer.
    Leaves are compared on the host, one at a time: on a mesh the engines
    and the trainer hold them on different devices."""
    import jax
    pipe.trainer_stage.failed = True     # no further optimizer steps
    tv = pipe.trainer.version
    try:
        pipe.loop.run(until=lambda: all(e.version == tv
                                        for e in pipe.engines),
                      max_events=max_events)
    except RuntimeError:
        pass
    bad = sum(abs(tv - int(e.version)) for e in pipe.engines)
    tp = jax.tree_util.tree_leaves(pipe.trainer.params)
    eps = [jax.tree_util.tree_leaves(e.params) for e in pipe.engines]
    for i, b in enumerate(tp):
        b = np.asarray(b)
        for leaves in eps:
            bad += int(not np.array_equal(np.asarray(leaves[i]), b))
    return bad


def segments(batches: List[Dict[str, np.ndarray]]) -> List[Dict[str, np.ndarray]]:
    """The rollouts inside packed batches, in order."""
    out = []
    for b in batches:
        for r in range(b["segment_ids"].shape[0]):
            seg = b["segment_ids"][r]
            for s in range(1, int(seg.max()) + 1):
                idx = seg == s
                out.append({k: b[k][r][idx] for k in b})
    return out


def engine_sample(batches, seed: int, n: int) -> List[Dict[str, np.ndarray]]:
    """Rollouts every sampled token of which carries weight version 0 (the
    seed's weights), a sample drawn from the seed with the longest in it."""
    segs = [s for s in segments(batches)
            if (s["weight_versions"][s["loss_mask"] > 0] == 0).all()
            and (s["loss_mask"] > 0).any()]
    if not segs:
        return []
    longest = int(np.argmax([len(s["tokens"]) for s in segs]))
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 64), 11]))
    rest = [i for i in range(len(segs)) if i != longest]
    pick = [longest] + list(rng.choice(rest, size=min(n - 1, len(rest)),
                                       replace=False))
    return [segs[i] for i in pick]


def program_readings(pipe, rec, cell, seed: int) -> dict:
    n = cell.workload["check"]["engine_rollouts"]
    sample = engine_sample(rec.batches, seed, n)
    return {
        "install_mismatch": install_mismatch(pipe),
        "losses": [float(m["loss"]) for m in rec.metrics[:3]],
        "grad_norms": rec.grad_norms,
        "change_norms": rec.change_norms,
        "batches": rec.batches[:3],
        "rollouts": sample,
        "rollout_lps": [s["behavior_logprobs"] for s in sample],
    }


def reference_readings(cell, seed: int, batches, rollouts, prec: str = "f32",
                       keep_rows=None) -> dict:
    wl, c = cell.workload, cell.config
    mesh = W.cell_mesh(wl, cell.chips)
    tr = R.train_steps(c, seed, batches, wl["optimizer"], wl["rl"], prec,
                       keep_rows=keep_rows, mesh=mesh)
    lps = R.rollout_logprobs(c, seed, rollouts, cell.mix["max_len"], prec,
                             mesh=mesh)
    return {"losses": tr["losses"], "grad_norms": tr["grad_norms"],
            "raw_grad_norms": tr["raw_grad_norms"],
            "change_norms": tr["change_norms"], "rollout_lps": lps}


def leaf_gap(got: Dict[str, float], ref: Dict[str, float],
             keys: Optional[List[str]] = None) -> float:
    keys = sorted(ref) if keys is None else keys
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(got[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def counted_leaves(ref: dict) -> List[str]:
    g = ref["raw_grad_norms"]
    med = float(np.median(list(g.values())))
    return sorted(k for k, v in g.items() if v >= UPDATE_LEAF_FLOOR * med)


def numbers(got: dict, ref: dict, rollouts) -> Dict[str, float]:
    """Every compared number of `got` (the program, or the control, or a
    planted fault) against the reference."""
    out = {}
    gaps = []
    for r, lp_got, lp_ref in zip(rollouts, got["rollout_lps"],
                                 ref["rollout_lps"]):
        sampled = r["loss_mask"] > 0
        gaps.append(float(np.max(np.abs(lp_got[sampled] - lp_ref[sampled]))))
    out["engine_lp_gap"] = max(gaps) if gaps else math.inf
    out["grad_gap"] = (leaf_gap(got["grad_norms"], ref["grad_norms"])
                       if got["grad_norms"] else math.inf)
    out["update_gap"] = (leaf_gap(got["change_norms"], ref["change_norms"],
                                  counted_leaves(ref))
                         if got["change_norms"] else math.inf)
    return out


def compare(readings: dict, cell, seed: int) -> Dict[str, float]:
    ref = reference_readings(cell, seed, readings["batches"],
                             readings["rollouts"])
    out = numbers(readings, ref, readings["rollouts"])
    out["install_mismatch"] = float(readings["install_mismatch"])
    return out


def verdict(chk: Dict[str, dict]) -> bool:
    """Correct when every number is finite and within its limit. A number
    without a limit is not correct."""
    return all(v["limit"] is not None and math.isfinite(v["value"])
               and v["value"] <= v["limit"] for v in chk.values())
