"""Readings the limits of `correct` are set from, on the chip at the cell's
own size (the benchmark's own runs never run this):

    python benchmarks/chip/control.py --workload granite2b-rl-long \\
        --seeds 1 2 3 ... --out chiprun_out/control.jsonl

For every seed, in one process: the program's sound readings (set-up of
a run: the first wave and the first optimizer steps, no window), then,
against the same float32 reference,
- the control: the reference in the program's place, computed in float8
  (e4m3, one scale per tensor), the precision below the bf16 the
  configuration states;
- the half-batch fault: the reference with half the packed rows left out
  and the mean taken over the rest;
- the altered-token fault: one sampled token of every compared rollout
  replaced by another, its carried logprob kept.
(A step that returns its state unchanged reads 1 on update_gap and
grad_gap by construction and needs no run; a publication that leaves
out the exchange between chips is `install_mismatch`'s, shown by a CPU
test.) On a cell with a mesh the program and every reference run on the
cell's chips.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def loss_gap(got, ref) -> float:
    """The worst step's |loss - reference| / |reference|."""
    return max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(got, ref))


def altered(rollouts, vocab: int):
    out = []
    for r in rollouts:
        r = dict(r)
        idx = [i for i, m in enumerate(r["loss_mask"]) if m > 0]
        i = idx[len(idx) // 2]
        tok = r["tokens"].copy()
        tok[i] = (int(tok[i]) + 1 + vocab // 2) % vocab
        r["tokens"] = tok
        out.append(r)
    return out


def readings_for_seed(cell, seed: int, log) -> dict:
    import jax
    import checks
    import harness
    import reference
    import weights
    t0 = time.perf_counter()
    pipe, rec, _ = harness.build(cell, seed, interpret=False)
    harness.warm_up(pipe, cell)
    jax.block_until_ready(pipe.trainer.state.params)
    prog = checks.program_readings(pipe, rec, cell, seed)
    del pipe, rec
    gc.collect()
    t1 = time.perf_counter()
    ro, b = prog["rollouts"], prog["batches"]
    ref = checks.reference_readings(cell, seed, b, ro)
    t2 = time.perf_counter()
    row = {"seed": seed, "setup_s": t1 - t0, "reference_s": t2 - t1}
    row["program"] = checks.numbers(prog, ref, ro)
    row["program"]["install_mismatch"] = prog["install_mismatch"]
    ctl = checks.reference_readings(cell, seed, b, ro, prec="fp8")
    row["control"] = checks.numbers(ctl, ref, ro)
    rows = b[0]["tokens"].shape[0]
    half = checks.reference_readings(cell, seed, b, ro,
                                     keep_rows=list(range(rows // 2)))
    row["half_batch"] = checks.numbers(half, ref, ro)
    alt = altered(ro, cell.config["vocab_size"])
    lps = reference.rollout_logprobs(
        cell.config, seed, alt, cell.mix["max_len"],
        mesh=weights.cell_mesh(cell.workload, cell.chips))
    row["altered_token"] = {"engine_lp_gap": checks.numbers(
        prog, dict(ref, rollout_lps=lps), ro)["engine_lp_gap"]}
    # the three steps' losses are read but not compared: the float8
    # control reads no more than three times what sound runs read
    row["loss_gap"] = {name: loss_gap(got["losses"], ref["losses"])
                       for name, got in (("program", prog), ("control", ctl),
                                         ("half_batch", half))}
    log(json.dumps(row))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import harness
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    out = open(args.out, "a") if args.out else None

    def log(s):
        print(s, flush=True)
        if out:
            out.write(s + "\n")
            out.flush()

    rows = [readings_for_seed(cell, s, log) for s in args.seeds]
    summary = {}
    for kind in ("program", "control", "half_batch", "altered_token",
                 "loss_gap"):
        keys = rows[0][kind].keys()
        summary[kind] = {k: {"max": max(r[kind][k] for r in rows),
                             "min": min(r[kind][k] for r in rows)}
                         for k in keys}
    log(json.dumps({"summary": summary, "seeds": args.seeds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
