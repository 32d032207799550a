"""The blocking device-to-host fetch per event: ``xla.fetch`` spans inside the
``backend.contention_field`` spans of the execute phase, a part of
``backend_ms_per_event``."""

from spans import events, inside, named


def _dispatch(s):
    return (s["name"] == "backend.contention_field"
            and s.get("args", {}).get("phase") == "execute")


def read(ctx):
    spans = ctx["spans"]
    fetches = named(spans, lambda s: s["name"] == "xla.fetch")
    if not fetches:
        return None
    return 1e-3 * inside(named(spans, _dispatch), fetches) / events(ctx)
