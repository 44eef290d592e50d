"""Kernels: of the blocks that the rows through a selecting attention could
see, the share they attend (engine counters ``sparse_selected`` /
``sparse_visible`` over the window, prefill and decode rows alike). A program
without the counters reports nothing."""


def read(ctx):
    stats = ctx["stats"]
    if not stats.get("sparse_visible"):
        return None
    return 100.0 * stats["sparse_selected"] / stats["sparse_visible"]
