"""One run of one cell: one process that holds the chip for its whole life.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to measure without a TPU (there is no CPU fallback and no flag for
one; the CPU tests call the cells' functions with a tiny configuration).
Ends with one JSON object as the last line on standard output.
"""

from __future__ import annotations

import time

STARTED = time.monotonic()   # set-up counts from here: imports are part of it

import argparse
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness

    try:
        # The program's own helper: JAX_COMPILATION_CACHE_DIR where the
        # machine sets it, else .jax_cache/ in the checkout - a fixed path.
        from gpt_2_distributed_tpu.compile_cache import ensure_compile_cache

        cache_dir = ensure_compile_cache()
        import jax

        # Every program, however quick to compile, is read back next time.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

        cell = harness.load_cell(args.workload)
        device = harness.require_tpu(cell["chips"])
        harness.load_peaks(device["kind"])
        print(f"device: platform={device['platform']} kind={device['kind']} "
              f"count={device['count']} | compile cache: {cache_dir}", flush=True)
        compiles = harness.CompileCounter()
        result = harness.runner_of(cell).run(
            cell, args.seed, args.seconds, bool(args.trace), device, STARTED, compiles)
    except (harness.RunFailed, LookupError, ImportError, FileNotFoundError) as exc:
        print(f"benchmark run failed: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 1
    harness.print_result(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
