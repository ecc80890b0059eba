"""Share of the decode cache's bytes that the engine's compiled decode step
writes back into the cache it was given, in place: the counter
``serve.cache_aliased`` of the program's recorder (``repro.tracing``),
added once by every ``generate`` call that decodes, over the traced
window's ``serve.generate`` calls. 100 when the step updates the whole
cache (K/V, SSM state and conv window) without a copy."""


def read(run):
    try:
        from repro.tracing import snapshot
    except ImportError:          # a program without the recorder
        return None
    snap = snapshot()
    calls = snap["names"].get("serve.generate", {}).get("count", 0)
    share = snap["counters"].get("serve.cache_aliased")
    if not calls or share is None:
        return None
    return 100.0 * share / calls
