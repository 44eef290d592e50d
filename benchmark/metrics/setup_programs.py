"""Program set-up: programs built before the window opened, each compiled
or read back from the persistent cache (the backend-compile events of the
program's compile watch). A program without the watch reports nothing."""


def read(ctx):
    try:
        from gpt_2_distributed_tpu.obs import compile_watch
    except ImportError:
        return None
    summary = compile_watch.get_watch().summary(before=ctx["window"][0])
    return summary["programs"] or None
