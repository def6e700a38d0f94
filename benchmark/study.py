"""Readings for a cell's limits, on the chip at the cell's own size.

``python3 benchmark/study.py --workload <cell> --seeds 1,2,3 [--controls 3]``
prints, per seed, the numbers ``correct`` compares for (a) the program
against the reference at the precision the configuration states: the lower
reading; and, on the first ``--controls`` seeds, for the reference put in
the program's place (b) computed in the nearest precision below (fp8
operands for bf16) and, on the first ``--faults`` seeds, (c) with half of
every batch left out and (d) with the exchange left out, every node keeping
what it trained: the upper readings.
A state left unchanged reads 1 by construction.  First it prints, per
format, whether the reference's rounding rounds on this device
(``reference/precision.py rounding_probe``).  One process, so the programs
compile once.  The benchmark's own runs never run this.
"""

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

LOWER_PRECISION = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--faults", type=int, default=3,
                        help="seeds on which the two faults are read too")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    from benchmark import harness, run
    from benchmark import inputs as cell_inputs
    from benchmark.cells import Cell
    from benchmark.reference import round as ref_round

    from benchmark.reference import precision

    cell = Cell(args.workload)
    run.require_chips(cell.chips)
    print(json.dumps({"rounding": precision.rounding_probe()}), flush=True)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        spans = harness.Spans()
        network, inputs, captured, one_call = harness.first_calls(
            cell, seed % (2**31 - 1), spans
        )
        job = harness.reference_job(cell, inputs)
        peak_after_first_calls = harness.memory_peak_bytes()
        harness.free(network)
        del network, one_call
        cell_inputs.draw_again(inputs, cell)
        off = cell_inputs.problems(inputs, cell)
        rounds = len(captured["loss"])
        program = harness.program_numbers(captured, inputs)
        del captured
        reference = ref_round.run(inputs, job, rounds=rounds)
        # A stand-in's run needs the device to itself, as the run's own
        # reference has it: what is compared later waits on the host.
        reference["trained_first"] = harness.to_host(reference["trained_first"])
        def leaves(run):
            """Every leaf's first-update norms [run's, reference's]."""
            update = ref_round.first_update(
                run["state_first"], inputs, job, reference["trained_first"]
            )
            return {k: [update["got"][k], update["want"][k]] for k in update["want"]}

        row = {"seed": seed, "inputs_off": off,
               "peak_after_first_calls": peak_after_first_calls,
               "seconds": spans.seconds,
               "program": harness.compare(program, reference, inputs, job),
               "loss": [program["loss"], reference["loss"]],
               "reference_change": reference["change"],
               "leaves": {"program": leaves(program)}}
        del program
        stand_ins = []
        if i < args.controls:
            stand_ins.append(("control", dataclasses.replace(
                job, compute_dtype=LOWER_PRECISION[job.compute_dtype])))
        if i < args.faults:
            stand_ins += [
                ("half_batch", dataclasses.replace(job, fault="half_batch")),
                ("no_exchange", dataclasses.replace(job, fault="no_exchange")),
            ]
        for label, other in stand_ins:
            stand_in = ref_round.run(inputs, other, rounds=rounds, keep_first=True)
            del stand_in["trained_first"]
            stand_in["state_first"] = harness.to_host(stand_in["state_first"])
            row[label] = harness.compare(stand_in, reference, inputs, job)
            row["leaves"][label] = leaves(stand_in)
            del stand_in
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
