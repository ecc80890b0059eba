"""Host milliseconds per batch in which the serving engine leaves the device
without its work: from each sync's return inside ``generate`` to the return
of the engine's next dispatch, and after the last sync to the call's return
(the counter ``serve.exposed_s`` of the program's recorder,
``repro.tracing``), over the traced window's ``serve.generate`` calls."""


def read(run):
    try:
        from repro.tracing import snapshot
    except ImportError:          # a program without the recorder
        return None
    snap = snapshot()
    calls = snap["names"].get("serve.generate", {}).get("count", 0)
    if not calls:
        return None
    return 1e3 * snap["counters"].get("serve.exposed_s", 0.0) / calls
