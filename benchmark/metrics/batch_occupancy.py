"""Serving scheduler: mean slots in use per engine step of the window
(``ServingEngine.occupancy`` sampled after each step)."""


def read(ctx):
    samples = ctx["occupancy"]
    if not samples:
        return None
    return sum(samples) / len(samples)
