"""Readings that the limits in ``benchmark/limits/`` are set from, many
seeds in one process (one compile, one machine):

    python3 -m benchmark.calibrate --workload <name> --seeds 1,2,3 \
        --what program,control,fault --seconds <s> --out <file.jsonl>

``program``: the timed path against the reference, as a run compares them
(the lower reading is the largest over a dozen seeds or more). ``control``:
the reference put in the program's place, computed with the family's
``control_matmul`` - the nearest precision below the one the configuration
states, fp8 operands under GPT-2's bfloat16 (the upper reading is the
smallest it gives). ``fault`` (training): half of the
batch left out and the mean taken over the rest, planted in the reference
put in the program's place. A step that returns its state unchanged reads 1
on ``moved_norm_gap`` by that number's measure and needs no run.

Not part of a benchmark run; needs the chip like one.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time


def training(cell, seed, what):
    """One seed's readings of a training cell."""
    from benchmark import check, harness, train_cell

    leaf_norms = cell["reference"].leaf_norms
    spans = harness.Spans()
    trainer = train_cell.Trainer(cell, seed, spans)
    try:
        observed = train_cell.first_steps(
            trainer, int(cell["mix"]["reference_steps"]))
    finally:
        trainer.close()
    lr, wd = trainer.lr, trainer.args.weight_decay
    trainer.free()
    del trainer
    gc.collect()
    t0 = time.monotonic()
    reference = train_cell.reference_numbers(cell, seed, observed, lr, wd)
    out = {"reference_s": time.monotonic() - t0,
           "losses": observed["losses"], "reference_losses": reference["losses"]}
    if "program" in what:
        got = check.training_numbers(observed, reference, leaf_norms)
        out["program"] = got["numbers"]
        out["program_leaves"] = got["leaves"]
    if "control" in what:
        control = train_cell.reference_numbers(
            cell, seed, observed, lr, wd, control=True)
        out["control"] = check.training_numbers(
            control, reference, leaf_norms)["numbers"]
    if "fault" in what:
        halved = dict(observed, batches=[
            (x[: len(x) // 2], y[: len(y) // 2]) for x, y in observed["batches"]])
        half = train_cell.reference_numbers(cell, seed, halved, lr, wd)
        out["fault_half_batch"] = check.training_numbers(
            half, reference, leaf_norms)["numbers"]
    return out


def serving(cell, seed, what, seconds):
    """One seed's readings of a serving cell, over a window of ``seconds``."""
    from benchmark import harness, serve_cell

    spans = harness.Spans()
    profiler = harness.ProfilerWindow(cell["name"], spans, False)
    engine, driver = serve_cell.build_engine(cell, seed)
    try:
        serve_cell.warm_up(cell, engine, driver)
        seen, *_ = serve_cell.run_window(
            cell, seed, seconds, engine, driver, spans, profiler)
    finally:
        driver.close()
    finished = [s for s in seen if s.done and s.handle.finish_reason == "length"]
    sample = serve_cell.sample_for_check(cell, finished, seed)
    del engine, driver
    gc.collect()
    # one reference for both readings: its weights are made once
    logits = cell["reference"].serving_reference(cell["sizes"], seed)
    out = {"finished": len(finished), "sampled": len(sample)}
    t0 = time.monotonic()
    gaps = serve_cell.logit_gaps(cell, seed, sample, logits=logits)
    out["reference_s"] = time.monotonic() - t0
    out["checked_tokens"] = int(len(gaps))
    if "program" in what:
        out["program"] = {"token_logit_gap": float(gaps.max()),
                          "tokens_off_best": int((gaps > 0).sum())}
    if "control" in what:
        rough = serve_cell.logit_gaps(
            cell, seed, sample, control=True, logits=logits)
        out["control"] = {"token_logit_gap": float(rough.max()),
                          "tokens_off_best": int((rough > 0).sum())}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.calibrate")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--what", default="program,control,fault")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    from benchmark import harness

    cell = harness.load_cell(args.workload)
    harness.require_tpu(cell["chips"])
    what = set(args.what.split(","))
    sink = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        sink = open(args.out, "a")
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            if harness.runner_name(cell) == "train_cell":
                row = training(cell, seed, what)
            else:
                row = serving(cell, seed, what, args.seconds)
            row = {"workload": args.workload, "seed": seed, **row}
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
