"""Host milliseconds per search spent in the engine before the device wait:
plan lookup plus the enqueue of the plan's stages (``engine.stage_us``
histograms, stages ``plan_lookup`` and ``execute``, over the traced window)."""


def read(ctx):
    st = ctx["engine"]
    calls = st.get("execute", (0.0, 0))[1]
    if not calls:
        return None
    us = st.get("plan_lookup", (0.0, 0))[0] + st["execute"][0]
    return us / calls / 1e3
