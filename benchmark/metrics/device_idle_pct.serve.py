"""``device_idle_pct.train`` as the backlog cells read it: the same share
of the traced window, under a name of its own because there it moves
``serve_tok_s``."""

from benchmark.harness import load_reader

read = load_reader("device_idle_pct.train")
