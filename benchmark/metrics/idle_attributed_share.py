"""Device: the share of the device's idle time that falls under a named
host phase, over the whole traced run. Busy is the union of
[`launchNs`, `readyNs`] of every DeviceDispatch span (the program's own
view, an upper bound on the device's); each gap is laid against the
`startNs`-placed phases of the query whose launch ends it
(span_phases.idle_by_phase). Prints the seconds a phase to stderr, and
the program's own idle share, to set beside device_idle_share."""
import sys

from span_phases import idle_by_phase


def read(ctx):
    out = idle_by_phase(ctx["records"])
    if out is None or not out["idle_ns"]:
        return None
    named = sum(out["phases"].values())
    by_size = sorted(out["phases"].items(), key=lambda kv: -kv[1])
    print("bench: idle by host phase: "
          + " ".join(f"{k} {v / 1e9:.3f}" for k, v in by_size)
          + f" unattributed {(out['idle_ns'] - named) / 1e9:.3f}"
          + f"; the program's own idle share "
          f"{100.0 * out['idle_ns'] / out['window_ns']:.1f}% of "
          f"{out['window_ns'] / 1e9:.1f} s", file=sys.stderr, flush=True)
    return 100.0 * named / out["idle_ns"]
