"""Most over fewest completions of a client in the window, over the
traffic's ``clients`` closed-loop clients: 1.0 is fair.  Every client sends
the same rounds of kinds, so a client that completes fewer was served later:
it is where a scheduler that hands every free slot to the oldest job would
show.  Nothing to read with one client, or where a client completed nothing
(that run reads ``failed`` or a short ``attempted``)."""

UNIT, BETTER, SOURCE = "ratio", "lower", "host_clock"
LAYER, MOVES = "client", "query_geomean_s"


def read(run):
    clients = int((run.get("traffic") or {}).get("clients", 1))
    done = [sum(q.get("client") == c for q in run["window"]) for c in range(clients)]
    if clients < 2 or min(done) == 0:
        return None
    return max(done) / min(done)
