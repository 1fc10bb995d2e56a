"""Server transport + executor: the ServerRequest span minus the
DeviceDispatch spans' launch + fetch (launch_fetch_ms), median. Under
load it is mostly the wait for the dispatch ring."""
from metrics import launch_fetch, median_or_none, per_query, span_sum


def read(ctx):
    def one(r):
        request = span_sum(r["trace"], "ServerRequest")
        launch = launch_fetch(r["trace"])
        if request is None or launch is None:
            return None
        return request - launch
    return median_or_none(per_query(ctx["records"], one))
