"""The accelerator this process holds, and where its compiled programs go.

A chip belongs to one process at a time, so only a process that is about
to run the engine may import this module (it imports jax); launchers,
brokers, controllers and clients must not.
"""
from __future__ import annotations

import importlib.metadata
import math
import os
import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jaxlib

#: the persistent XLA compile cache when JAX_COMPILATION_CACHE_DIR does
#: not place it: ONE fixed, git-ignored directory inside the checkout.
#: The path is part of the cache key, so it must never move between runs
#: (no tempdir, pid or timestamp).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def configure_compile_cache() -> None:
    """The one place the compile cache is placed; the engine constructor
    calls it, so it runs before a process's first engine compile (JAX
    decides once, at its first compile, whether a cache is in use). A
    directory given from outside wins: JAX reads JAX_COMPILATION_CACHE_DIR
    itself, and nothing is set here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)


def _libtpu_version() -> Optional[str]:
    try:
        return importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        return None


def device_report(devices: Sequence) -> Dict[str, Any]:
    """What the engine's devices are, as JAX reports them: platform,
    device_kind, count, the installed stack, per-device memory_stats
    (None where the backend keeps none, e.g. XLA:CPU), the compile cache
    in use with its entry count, and whether the native library loaded."""
    from pinot_tpu import native
    memory = []
    for d in devices:
        stats = d.memory_stats() or {}
        memory.append({
            "device": f"{d.platform}:{d.id}",
            "bytes_limit": stats.get("bytes_limit"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    cache_dir = jax.config.jax_compilation_cache_dir
    entries = 0
    if cache_dir and os.path.isdir(cache_dir):
        entries = sum(1 for n in os.listdir(cache_dir)
                      if n.endswith("-cache"))
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _libtpu_version(),
        "x64": bool(jax.config.jax_enable_x64),
        "memory": memory,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": entries,
        "native_lib": "built" if native.lib is not None else "absent",
    }


def device_line(report: Dict[str, Any]) -> str:
    """The one start-up line a device-path server prints."""
    return (f"device engine: platform={report['platform']} "
            f"device_kind={report['device_kind']!r} "
            f"devices={report['count']} "
            f"hbm_bytes_limit={report['memory'][0]['bytes_limit']} "
            f"jax={report['jax']} jaxlib={report['jaxlib']} "
            f"libtpu={report['libtpu']} "
            f"compile_cache_dir={report['compile_cache_dir']} "
            f"native_lib={report['native_lib']}")


#: bytes an element of an HLO primitive type
_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                 "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                 "u64": 8, "f64": 8}
_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ([a-z]\w*)\[([\d,]*)\]")
_HLO_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
    r"(?:-start)?\(")


def collective_bytes(hlo: str) -> Tuple[int, int]:
    """(bytes, the all-gathers' part of them) that ONE device hands to
    the collectives of a compiled program each time it runs, read from
    the program's own text (`Compiled.as_text()`, the module after
    partitioning): the operands of every all-reduce, all-gather,
    all-to-all, collective-permute and reduce-scatter, started or
    synchronous. A program with no collective reads (0, 0)."""
    sizes: Dict[str, int] = {}
    for line in hlo.splitlines():
        m = _HLO_DEF.match(line)
        if m and m.group(2) in _HLO_ITEMSIZE:
            sizes[m.group(1)] = _HLO_ITEMSIZE[m.group(2)] * math.prod(
                int(d) for d in m.group(3).split(",") if d)
    total = gathered = 0
    for line in hlo.splitlines():
        m = _HLO_COLLECTIVE.search(line)
        if m is None:
            continue
        depth, end = 1, m.end()
        while depth and end < len(line):
            depth += {"(": 1, ")": -1}.get(line[end], 0)
            end += 1
        moved = sum(sizes.get(name, 0) for name in
                    re.findall(r"%([\w.\-]+)", line[m.end():end]))
        total += moved
        if m.group(1) == "all-gather":
            gathered += moved
    return total, gathered
