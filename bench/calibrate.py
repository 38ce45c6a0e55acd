"""Readings the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload qwen3-1.7b.topk.1chip \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --controls 3

On the chip, at the cell's own size, in one process (one compile): for
every seed, the program's three set-up steps against the reference (the
lower readings); for the first ``--controls`` seeds also the control (the
reference with float8 matmuls in the program's place) and each fault the
cell can have, planted in the reference put in the program's place (the
upper readings).  A state left unchanged reads 1 on the change of the
parameters, of x_hat and of s by construction and needs no run.  One JSON
line per reading, then the largest lower and the smallest upper reading of
each number and the limits :func:`limits` sets from them; ``--write``
writes those to ``bench/limits/<cell>.json``.  The benchmark's own runs
never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: an upper reading counts where the control reads this many times the
#: lower reading, or a fault reads FAULT_RATIO times it
CONTROL_RATIO, FAULT_RATIO = 3.0, 10.0
#: numbers a state left unchanged reads 1 on (a norm gap of 1)
UNCHANGED_READS_ONE = ("step_gap", "hat_gap", "s_gap")


def limits(lower, upper):
    """The limit of each number from its readings: the upper reading is the
    smallest of the control's (where at least CONTROL_RATIO times the
    lower), the faults' (where at least FAULT_RATIO times it) and, for the
    norms of the change, of x_hat and of s, the unchanged state's 1; the
    limit is lower^(1/3) * upper^(2/3), so that more of the room lies above
    the lower reading, to one significant digit (two where one would leave
    the open interval).  A number with no upper reading gets no limit.
    Returns (limits, the upper reading each was set from, whether the
    control fails some limit)."""
    out, ups = {}, {}
    for k, lo in lower.items():
        cands = []
        for kind, reads in upper.items():
            v = reads.get(k)
            ratio = CONTROL_RATIO if kind == "control" else FAULT_RATIO
            if v is not None and v >= ratio * lo:
                cands.append(v)
        if k in UNCHANGED_READS_ONE and 1.0 >= FAULT_RATIO * lo:
            cands.append(1.0)
        if not cands:
            continue
        up = min(cands)
        raw = lo ** (1 / 3) * up ** (2 / 3)
        lim = float(f"{raw:.0e}")
        if not lo < lim < up:
            lim = float(f"{raw:.1e}")
        out[k], ups[k] = lim, up
    control = upper.get("control", {})
    fails = any(control.get(k, 0.0) > v for k, v in out.items())
    return out, ups, fails


def calibrate(cell, seeds, controls: int, devs):
    """Print the readings of ``seeds`` (controls and faults on the first
    ``controls`` of them); return the summary."""
    import gc
    from bench import compare
    from bench import run as brun
    from bench.reference import Reference
    model, tr, lr = cell.model, cell.traffic, cell.config["lr"]
    reference = Reference(model, tr, lr, devs)
    variants = {"control": {"control": True},
                "half_batch": {"fault": "half_batch"},
                "altered": {"fault": "altered"}}
    if tr["nodes"] > 1:
        variants["no_exchange"] = {"fault": "no_exchange"}
    prog, compiled = brun.compile_step(cell, trace=False)
    lower, upper = {}, {}
    for n, seed in enumerate(seeds):
        state, _, _, first, key, read = brun.first_steps(cell, prog,
                                                         compiled, seed)
        del state
        gc.collect()
        t = time.perf_counter()
        ref = reference.run(key, first)
        ref_s = time.perf_counter() - t
        gaps = compare.gaps(read, ref)
        print(json.dumps({"seed": seed, "kind": "program", "gaps": gaps,
                          "reference_s": ref_s, "loss": read["loss"]}),
              flush=True)
        for k, v in gaps.items():
            lower[k] = max(lower.get(k, 0.0), v)
        if n >= controls:
            continue
        for kind, kwargs in variants.items():
            g = compare.gaps(reference.run(key, first, **kwargs), ref)
            print(json.dumps({"seed": seed, "kind": kind, "gaps": g}),
                  flush=True)
            for k, v in g.items():
                upper.setdefault(kind, {})
                upper[kind][k] = min(upper[kind].get(k, float("inf")), v)
    lim, ups, fails = limits(lower, upper)
    summary = {"lower": lower, "upper": upper, "limits": lim,
               "limit_upper": ups, "control_fails": fails,
               "seconds": time.perf_counter() - T0}
    print(json.dumps(summary), flush=True)
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--write", action="store_true",
                    help="write the limits to bench/limits/<cell>.json")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import cell as cells
    from bench import run as brun
    cell = cells.load(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        brun._fail(f"{cell.name} needs {cell.chips} TPU chips, found "
                   f"{len(devices)} {devices[0].platform}")
    jax.config.update("jax_compilation_cache_dir", brun.CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    summary = calibrate(cell, [int(s) for s in args.seeds.split(",")],
                        args.controls, devices[:cell.chips])
    if args.write:
        with open(os.path.join(ROOT, "bench", "limits",
                               cell.name + ".json"), "w") as f:
            json.dump(summary["limits"], f)
            f.write("\n")
    return 0 if summary["control_fails"] else 1


if __name__ == "__main__":
    sys.exit(main())
