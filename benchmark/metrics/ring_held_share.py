"""Dispatch ring: the share of the window's ring dispatches that were
HELD for an in-flight slot, in percent: `DeviceDispatch.heldMs` > 0 over
the dispatches that carry it (every one that rode the ring since PR 31;
an inline dispatch carries none). A held launch waited in the ring,
joining a batch, where it would else have queued on the device alone; the
time is part of `ring_wait_ms`. None where no dispatch rode the ring, or
the program has no such attribute."""
from judge import FALLBACK_OUTCOMES, spans


def read(ctx):
    held = [d["heldMs"] > 0 for r in ctx["records"]
            for d in spans(r.get("trace"), "DeviceDispatch")
            if d.get("heldMs") is not None
            and d.get("outcome") not in FALLBACK_OUTCOMES]
    return 100.0 * sum(held) / len(held) if held else None
