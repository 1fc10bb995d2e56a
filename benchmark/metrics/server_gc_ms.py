"""Server transport + executor: the collector's pauses inside the
server's requests, the MEAN a traced answered query of the sum of its
`ServerRequest.gcPauseMs` (0 where no collection fell inside). A program
without the probe gives None. Prints the same stderr lines as
`broker_gc_ms`, for the server process."""
from metrics.broker_gc_ms import gc_report


def read(ctx):
    return gc_report(ctx, "ServerRequest", "server_gc_ms")
