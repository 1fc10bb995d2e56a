"""The hour bucket of a fleet's readings written in time order (TSBS
devops: every member of the fleet reports once a tick, tick after tick,
and a segment is one stretch of that stream): row r of a segment belongs
to tick r // fleet, and a segment spans `segment_seconds` of the stream
whatever its docs (a small table is the same layout with the ticks
further apart: 6,480,000 docs of 4000 hosts are 1,620 ticks of 10 s).
The `table_segments` stretches lie end to end; which of them segment i
holds is i with its bits reversed (0, 8, 4, 12, 2, ... of 16), so that
the whole table is the whole stream and the first few segments alone, a
test's small table, are spread over all of it. Either way the table is
time-partitioned: a segment holds a few hours, every host in each, and a
time filter prunes the others by the column's min and max.

The segment's index is not among a generator's arguments; it is read
back from the generator it is given, which datagen.make_columns seeds
with [seed, segment]."""
import numpy as np


def layout(rng, docs, spec, pools):
    """(the segment's stretch of the stream, fleet size, tick of every
    row, ticks a segment)."""
    segment = int(rng.bit_generator.seed_seq.entropy[1])
    bits = spec["table_segments"].bit_length() - 1
    if spec["table_segments"] != 1 << bits or segment >> bits:
        raise ValueError(f"segment {segment} of a table of "
                         f"{spec['table_segments']} stretches (a power of 2)")
    stretch = int(format(segment, f"0{bits}b")[::-1], 2) if bits else 0
    fleet = len(pools[spec["fleet"]])
    tick = np.arange(docs, dtype=np.int64) // fleet
    return stretch, fleet, tick, -(-docs // fleet)


def generate(rng, docs, spec, pools, made):
    lo, hi, span = spec["lo"], spec["hi"], spec["segment_seconds"]
    stretch, _fleet, tick, ticks = layout(rng, docs, spec, pools)
    second = stretch * span + tick * span // ticks
    codes = (second // 3600).astype(np.int32)
    if codes[-1] > hi - lo:
        raise ValueError(f"stretch {stretch} ends in hour {lo + codes[-1]}, "
                         f"past the column's last, {hi}")
    return codes + np.int32(lo), codes, np.arange(lo, hi + 1)
