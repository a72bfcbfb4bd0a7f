"""How late requests reach ``Engine.submit`` after they fall due: the
harness's front end waits for a free slot and for pages, and the engine
is synchronous, so this is step granularity plus queueing.  A request
due in the window and still queued at its close counts at its wait so
far."""
from chipbench.stats import percentile

NAME = "submit_lag_ms_p95"
UNIT = "ms"
LAYER = "front end"
MOVES = "ttft_p85_ms"
SOURCE = "host_clock"


def read(ctx):
    lags = ctx.host["submit_lags_s"]
    return 1e3 * percentile(lags, 95) if lags else None
