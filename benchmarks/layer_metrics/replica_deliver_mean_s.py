"""Mean time of the answer's way OUT of the replica: from the instant
the engine resolved the request's future (a done-callback stamps it in
ReplicaServer.serve_submit) to the gateway's serve_release of the
fetched result, per released request: stage_deliver_sum_s over
stage_deliver_n, differenced.  The serve_wait long poll's wake-up and
the serve_fetch and serve_release round trips, seen from inside: the
part of gateway_overhead_p50_s that lies after the engine.  The way in
and the gateway's own thread hop are the rest.  None where the program
has no such counters or released nothing."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("stage_deliver_n"):
        return None
    return c["stage_deliver_sum_s"] / c["stage_deliver_n"]
