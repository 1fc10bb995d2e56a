"""Dispatch ring: mean `DeviceDispatch.batchSize` over the window's
dispatches: how many queries one launch carried."""
from judge import spans


def read(ctx):
    sizes = [d["batchSize"] for r in ctx["records"]
             for d in spans(r.get("trace"), "DeviceDispatch")
             if d.get("batchSize") is not None]
    return sum(sizes) / len(sizes) if sizes else None
