"""Engine: plan + stage: how long a query held the staging lock, median:
`DeviceDispatch.lockHeldMs`, acquired -> released, summed over a pass's
holds (the block look-ups and the parameter-cache probe, plus the
insert of what a probe's miss built). Since PR 33 the lock guards the
stager and nothing else; this is the number that says it is narrow:
`engine_lock_wait_ms` is the queue it makes, `staging_params_ms` what
left it. A program without the attribute (PR 32 and before) gives
None."""
from metrics import median_or_none, per_query
from span_phases import dispatch_sum


def read(ctx):
    return median_or_none(per_query(
        ctx["records"], lambda r: dispatch_sum(r["trace"], "lockHeldMs")))
