"""Readings from which a cell's limits for ``correct`` are set.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2] [--out FILE]

In one process: for each seed, the program's first three steps at the
cell's own sizes against the float32 reference (the lower readings);
for each control seed, the reference computed in scaled float8 put in
the program's place (the upper readings); for each fault seed, the
program with each fault of ``faults.py`` planted under its step. One
JSON line per reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

    from bench import check, faults, harness, program

    cell = harness.load_cell(args.workload)
    devs = harness.devices_for(cell, require_tpu=True)
    program.init_compile_cache()
    built = program.build(cell.config, cell.traffic, devs)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    faulted = [int(s) for s in args.fault_seeds.split(",") if s]
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    def reference(seed, batches, bits, precision):
        return check.reference_readings(
            cell.config["family"], cell.config, cell.traffic,
            built.abstract_params, seed, batches, bits, devs, precision)

    def program_readings(step_of, seed):
        with program.set_mesh(built.mesh):
            params, opt, gen, bits = harness.make_state(cell, built, seed)
            params, opt, prog, batches = harness.first_steps(
                step_of, gen, params, opt, seed)
            del params, opt
        gc.collect()
        return prog, batches, bits

    for seed in seeds:
        t0 = time.perf_counter()
        prog, batches, bits = program_readings(built, seed)
        ref = reference(seed, batches, bits[:check.STEPS], "float32")
        emit({"workload": cell.name, "seed": seed, "kind": "program",
              **check.compare(prog, ref),
              "loss": prog["loss"].tolist(), "ref_loss": ref["loss"].tolist(),
              "seconds": time.perf_counter() - t0})
        if seed in controls:
            ctl = reference(seed, batches, bits[:check.STEPS], "float8")
            emit({"workload": cell.name, "seed": seed, "kind": "control",
                  **check.compare(ctl, ref), "loss": ctl["loss"].tolist()})
        if seed in faulted:
            for name, fault in faults.FAULTS.items():
                broken = dataclasses.replace(built, step=fault(built))
                got, _, _ = program_readings(broken, seed)
                emit({"workload": cell.name, "seed": seed,
                      "kind": f"fault:{name}", **check.compare(got, ref),
                      "loss": got["loss"].tolist()})
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
