"""90th percentile time to first token: from when the serving harness took
a query to the engine's first token of its answer (the program's
``serve.ttft_s`` observations, ``ServingHarness._finish``), over the first
query's due time to the last answer, in ms.  Read only from a run traced
on an accelerator (a device plane in the trace), where the first token
waits on the device's prefill programs."""
import numpy as np

from repro import obs


def read(ctx):
    reg = getattr(obs, "REGISTRY", None)
    if reg is None or not ctx.records or not (ctx.trace or {}).get("devices"):
        return None
    a = min(r["due"] for r in ctx.records)
    b = max(r["end"] for r in ctx.records)
    x = [v for t, v in reg.observations("serve.ttft_s") if a <= t <= b]
    return float(np.percentile(x, 90) * 1e3) if x else None
