"""What the retrieve layer hands the device per search call, in MB (1e6
bytes): the growth of the program's ``db.h2d_bytes`` counter over that of
its ``db.search_calls`` counter (``repro.obs.REGISTRY``, both counted in
``JaxVectorDB._search_arrays``) from the first query's due time to the last
answer.  An array already on the device counts nothing.  Read only from a
run traced on an accelerator (a device plane in the trace): on the CPU the
arrays stay in host memory and no bytes cross to a device."""
from repro import obs


def growth(reg, name: str, a: float, b: float) -> float:
    """How much the process counter ``name`` grew over ``[a, b]``."""
    pts = reg.series(name)
    before = [p.value for p in pts if p.t < a]
    upto = [p.value for p in pts if p.t <= b]
    return (upto[-1] - (before[-1] if before else 0.0)) if upto else 0.0


def read(ctx):
    reg = getattr(obs, "REGISTRY", None)
    if reg is None or not ctx.records or not (ctx.trace or {}).get("devices"):
        return None
    a = min(r["due"] for r in ctx.records)
    b = max(r["end"] for r in ctx.records)
    calls = growth(reg, "db.search_calls", a, b)
    return growth(reg, "db.h2d_bytes", a, b) / calls / 1e6 if calls else None
