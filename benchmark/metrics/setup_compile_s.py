"""Program set-up: seconds of set-up the process spent tracing, lowering and
compiling programs or reading them back from the persistent cache - the
union of those intervals before the window opened, from the program's
compile watch. A program without the watch reports nothing."""


def read(ctx):
    try:
        from gpt_2_distributed_tpu.obs import compile_watch
    except ImportError:
        return None
    summary = compile_watch.get_watch().summary(before=ctx["window"][0])
    return summary["seconds"] or None
