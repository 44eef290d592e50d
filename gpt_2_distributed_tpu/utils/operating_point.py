"""Known-bad operating-point detection for the CLI drivers.

PERF_ANALYSIS.md §8: the unrolled (non-scan) 124M-class step at seq 1024
with grad_accum=16 hits an XLA scheduling cliff — MFU collapses to ~18%
versus ~50% at accum 12 or seq 2048 (the unrolled accumulation loop at that
exact shape triggers a pathological schedule). This module is the warning
for users who select the cliff explicitly via ``train.py`` flags.
"""

from __future__ import annotations

# The measured cliff coordinates. Deliberately exact-match (not a range):
# neighboring points (a12, a8, seq 2048) measured fine, so warning on
# anything broader would cry wolf.
_CLIFF_SEQ_LEN = 1024
_CLIFF_GRAD_ACCUM = 16

_WARNED: set[str] = set()


def accum_cliff_message(
    seq_len: int, grad_accum_steps: int, scan_layers: bool
) -> str | None:
    """The warning text when (seq_len, grad_accum, unrolled) sits on the
    known scheduling cliff, else None.

    Only the UNROLLED stack is affected — the lax.scan form compiles the
    accumulation loop differently and does not exhibit the collapse."""
    if scan_layers:
        return None
    if seq_len != _CLIFF_SEQ_LEN or grad_accum_steps != _CLIFF_GRAD_ACCUM:
        return None
    return (
        f"grad_accum_steps={_CLIFF_GRAD_ACCUM} at seq_len={_CLIFF_SEQ_LEN} "
        "with unrolled layers is a known XLA scheduling cliff (~18% MFU vs "
        "~50%, PERF_ANALYSIS.md §8); use --grad_accum_steps <= 12, "
        "--scan_layers on, or seq 2048"
    )


def warn_once(tag: str, message: str, printer=print) -> bool:
    """Emit ``message`` through ``printer`` at most once per process per
    ``tag``. Returns True when it printed. Callers gate on rank themselves
    (``is_primary()``) — this helper only dedupes."""
    if tag in _WARNED:
        return False
    _WARNED.add(tag)
    printer(f"warning: {message}")
    return True
