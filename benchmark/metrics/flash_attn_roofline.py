"""Kernels: the flash-attention forward and backward kernels' share of
their roofline over the traced window. The least time comes from the
shapes (``work.flash_attention_work``) of each kernel call counted in the
trace, with the heads and the head width that the family's
``attention_shapes`` gives; the time is the sum of those events' device
durations. The Pallas calls carry no ``name=``, so the events are found by
what the trace does show (looked at by hand, PR 24): Mosaic custom calls
whose instruction is named after the name stack, ``jvp__`` forward and
``transpose_jvp___`` backward. At this operating point they are the step's
only Pallas kernels."""

from benchmark import work

MOSAIC = 'custom_call_target="tpu_custom_call"'
FORWARD, BACKWARD = ("%jvp__", MOSAIC), ("%transpose_jvp__", MOSAIC)


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["trace_window_ns"]
    cfg, mix = ctx["cell"]["config_file"], ctx["cell"]["mix"]
    shapes = ctx["cell"]["reference"].attention_shapes(ctx["sizes"])
    shape = (cfg["train"]["micro_batch"], shapes["heads"], mix["seq_len"],
             shapes["head_dim"])
    spent, least = 0.0, 0.0
    for needle, backward in ((FORWARD, False), (BACKWARD, True)):
        seconds, calls = trace.kernel_seconds(needle, lo, hi)
        flops, nbytes = work.flash_attention_work(*shape, backward=backward)
        spent += seconds
        least += calls * work.roofline_seconds(flops, nbytes, ctx["peaks"])[0]
    return 100.0 * least / spent
