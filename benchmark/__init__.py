"""The benchmark of gpt2-tpu: ``python3 -m benchmark.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>``. See ``BENCHMARK.json`` and PERF.md."""
