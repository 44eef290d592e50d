"""How ``small_train.xplane.pb`` and ``small_serve.xplane.pb`` (beside this
file) were recorded, on the chip:

    chiprun -- python3 tests/benchmark/record_small_trace.py

Runs the harness's own training and backlog cells, traced, at a size small
enough that 50 ms of trace is a few hundred KB, through the real kernels
(flash forward/backward, paged decode), and keeps the ``.xplane.pb`` files
and a by-hand summary under ``chiprun_out/small_trace/``.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]
OUT = os.path.join(ROOT, "chiprun_out", "small_trace")


def main():
    from bench_tiny import tiny_cell
    from benchmark import harness, reduce_trace, serve_cell, train_cell

    kinds = sys.argv[1:] or ["train", "backlog"]   # which of the two to record
    harness.TRACE_SECONDS = 0.05
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    os.makedirs(OUT, exist_ok=True)
    report = {}
    for kind, runner, cell_name in (("train", train_cell, "train-124m-1k"),
                                    ("backlog", serve_cell, "serve-xl-backlog")):
        if kind not in kinds:
            continue
        cell = tiny_cell(
            kind, n_embd=128, n_head=2, vocab_size=512, n_positions=256,
            train={"flags": {"attention_impl": "auto"}},
            serve={"block_size": 16, "prefill_chunk": 32, "attn_impl": "auto"})
        if kind == "train":
            cell["mix"].update(seq_len=256, tokens_per_shard=65536)
        cell["per_layer"] = [m for m in manifest["per_layer"]
                             if cell_name in m["workloads"]]
        device = harness.require_tpu(1)
        keep = os.path.join(OUT, f"small_{'train' if kind == 'train' else 'serve'}.xplane.pb")
        os.environ["BENCH_KEEP_TRACE"] = keep
        result = runner.run(cell, 11, 0.5, True, device, time.monotonic(),
                            harness.CompileCounter())
        harness.print_result(**result)
        report[kind] = {k: result[k] for k in ("metrics", "device", "breakdown")}
        with open(keep.replace(".xplane.pb", ".summary.txt"), "w") as f:
            f.write(reduce_trace.summary(keep))
    with open(os.path.join(OUT, "expected.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
