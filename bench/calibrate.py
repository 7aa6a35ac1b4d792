"""Readings behind the limits of `correct`, on the chip, at a cell's
own size.  Not part of a benchmark run.

    python3 bench/calibrate.py --workload fig2_iid.fused --seeds 1,2,3

For each seed, in one process: the program's sound readings (its first
three rounds through the timed chunk program against the float32
reference: the lower readings), the control's (the reference one
precision step down, `reference.control`, put in the program's place), and the readings of faults
planted in the reference put in the program's place (a local step's
loss over half of its minibatch; the eval loss over half of the test
set).  A state left unchanged reads 1 on `update_gap` (and on
`moment_gap`) by construction and needs no run.  One JSON line per seed
and reading; with ``--out`` the lines also go to that file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def reference_as_program(cell, seed, lower: bool, fault=""):
    """Readings of the reference in the program's place: its outputs in
    the program's layout, compared with the float32 reference."""
    import jax
    import numpy as np

    from bench import compare
    from bench.harness import CHECK_ROUNDS
    from bench.inputs import make_inputs, model
    from bench.reference import Round, Setup, control

    cfg, tr = cell.config, cell.traffic
    S = tr["seeds_per_dispatch"]
    inp = make_inputs(seed, cfg, S)
    setup = Setup.from_config(cfg, tr, inp.d_mu_is, inp.d_is_ps)
    rnd = (control(setup, model(cfg), fault) if lower
           else Round(setup, model(cfg), np.float32, fault))
    runs = [rnd.run(jax.tree.map(lambda a: a[s], inp.params), inp.X, inp.Y,
                    inp.xte, inp.yte, inp.keys[s], CHECK_ROUNDS)
            for s in range(S)]
    stack = lambda trees: jax.tree.map(
        lambda *xs: np.stack([np.asarray(x, np.float32) for x in xs]),
        *trees)
    prog = {"losses": np.array([r[0] for r in runs]).T,
            "m1": stack([r[1]["m"] for r in runs]) if cfg["opt"] == "adam"
            else None,
            "theta3": stack([r[3] for r in runs])}
    theta0 = jax.device_get(inp.params)
    ref = [Round(setup, model(cfg), np.float32).run(
        jax.tree.map(lambda a: a[s], inp.params), inp.X, inp.Y, inp.xte,
        inp.yte, inp.keys[s], CHECK_ROUNDS) for s in range(S)]
    return compare.readings(prog, ref, theta0, cfg["opt"] == "adam")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--readings", default="program,control,half_batch,"
                    "half_test")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from bench import harness

    harness.enable_cache()
    cell = harness.find_cell(args.workload)
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        for what in args.readings.split(","):
            t0 = time.perf_counter()
            if what == "program":
                S = cell.traffic["seeds_per_dispatch"]
                inp = harness.make_inputs(seed, cell.config, S)
                prog = harness.build_program(cell, inp, seed)
                del inp
                losses, m1, theta3 = harness.setup_rounds(
                    prog, cell.config["opt"] == "adam")
                del prog
                r = harness.reference_readings(
                    cell, seed, {"losses": losses, "m1": m1,
                                 "theta3": theta3})
            elif what == "control":
                r = reference_as_program(cell, seed, True)
            else:
                r = reference_as_program(cell, seed, False, what)
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "reading": what, **r,
                               "seconds": time.perf_counter() - t0})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
