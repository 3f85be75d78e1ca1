"""Chip benchmark of PipelineRL: one run of one cell on the chips it names.

    python benchmarks/chip/run.py --workload granite2b-rl-long \\
        --seed 12345 --seconds 30 --trace 0

Runs only on a TPU (no CPU fallback): without one, or with fewer chips
than the cell asks for, it exits non-zero and prints no result. Each run
enables the compile cache (`JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`), makes the configuration's weights on the device
from `--seed`, builds the public `PipelineRL` with the benchmark's own
seeded prompt source and task, warms up until every program of the
window has compiled (a full wave of rollouts, the first optimizer steps
and a completed publication), drives the event loop for `--seconds` of
wall time, checks what the timed path produced against the plain
reference (`checks.py`, `reference.py`), and prints one JSON line last on
stdout:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

`--trace 0` reports the cell's end-to-end metrics; `--trace 1` traces the
window with the JAX profiler and reports the per-layer metrics instead.

Layout (a later PR adds files and `BENCHMARK.json` entries; it edits no
file that is here):

    configs/<config>.json    sizes as run, source, cuts (`reduced`), `assumed`,
                             `arch` (which module below runs it)
    arch/<arch>.py           one module per architecture: the program's
                             config, the weights' leaves and their mapping
                             to the program's tree, the reference block,
                             its matmul and attention counts
    traffic/<mix>.json       parameters of one traffic mix (traffic_gen.py)
    workloads/<cell>.json    engine, pipeline, optimizer settings, an
                             optional `mesh` over the cell's chips, and the
                             limits of the correctness check
    metrics/<metric>.py      one reader per per-layer metric: read(ctx)
                             returns a number, or None when it finds nothing
    peaks.py                 published peaks by device_kind
    flops.py                 operations and bytes from the shapes
    profile_reduce.py        trace -> busy time, kernel time, idle gaps
    program_spans.py         the program's spans placed on the trace
    weights.py               seeded weights, the `arch` loader, placement
                             on a mesh
    reference.py, checks.py  the plain reference and the comparison
    control.py               readings of the program, the control and the
                             planted faults on the chip, to set limits from
    tests/                   CPU tests of all of the above

A new cell costs chip time in every later check: on one chip unless what
it measures exists only across chips, and only with a `why` that names
the layer it exercises or bypasses.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to leave the profiler trace in")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, interpret=False,
                      trace_dir=args.keep_trace, keep_trace=bool(
                          args.keep_trace),
                      log=lambda s: print(s, file=sys.stderr, flush=True))
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
