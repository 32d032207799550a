"""The compiled backend's time per event: ``backend.contention_field`` spans in
their execute phase (host to device and back, with the device's work)."""

from spans import events


def read(ctx):
    us = sum(s["dur"] for s in ctx["spans"] if s["name"] == "backend.contention_field"
             and s.get("args", {}).get("phase") == "execute")
    return 1e-3 * us / events(ctx)
