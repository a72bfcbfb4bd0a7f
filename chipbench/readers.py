"""Helpers the per-layer metric readers share: device time of a program
or a kernel in the traced window, and roofline shares."""
from __future__ import annotations

from typing import Iterable, List, Optional

from chipbench import flops
from chipbench import trace as TRC

# Names the program gives its compiled pieces.  The prefill is a lambda
# jitted inside ``Engine.__init__``; a stable name for it is a tracing
# item for a later change.
STEP_PROGRAM = "step_fn"
PREFILL_PROGRAM = "lambda"
PAGED_KERNEL = "portable_paged_decode_attention"
FLASH_KERNEL = "portable_flash_attention"


def program_events(ctx, needle: str) -> List[TRC.Event]:
    return [e for e in ctx.modules if needle in e.name]


def kernel_events(ctx, name: str) -> List[TRC.Event]:
    return TRC.named(ctx.ops, name)


def share(least_s: float, took_s: float) -> Optional[float]:
    """100 * least / took, or nothing when nothing was timed."""
    if took_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / took_s


def per_call_scale(n_events: int, n_expected: int) -> float:
    """Calls seen in the trace over the calls the harness counted: the
    trace's edges can cut a step the harness still counted in."""
    return n_events / n_expected if n_expected else 0.0


def roofline_sum(costs: Iterable[dict], peaks) -> float:
    return sum(flops.roofline_seconds(c, peaks)["seconds"] for c in costs)
