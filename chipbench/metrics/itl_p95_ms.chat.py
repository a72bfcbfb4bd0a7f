"""The 95th percentile of chat's inter-token gaps, on the host clock, in
the traced run.  Chat's prompts are rounded up to 256-2048 tokens, so its
gaps fall in clusters: the plain decode step (~85 ms on a TPU v5e) and
steps that also wait for a prefill (near 110-130, 140-150 and 180-250
ms).  The 95th percentile lies in the thin stretch between the last two
(the 94th near 145 ms, the 96th near 185), so two runs of one seed read
153 and 166 ms: too unsteady for an end-to-end bound, kept here.  The
prefills that stretch these gaps also set new requests' first tokens."""
from chipbench.stats import percentile

NAME = "itl_p95_ms.chat"
UNIT = "ms"
LAYER = "engine"
MOVES = "ttft_p85_ms"
SOURCE = "host_clock"


def read(ctx):
    gaps = ctx.host["itl_gaps_s"]
    return 1e3 * percentile(gaps, 95) if gaps else None
