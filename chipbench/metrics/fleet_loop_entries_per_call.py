"""Entries of the engine's device loop per fleet call (`fleet.loop`
spans per `simulate_call`): 1, plus one for each doubling of the pools
and each compaction."""


def read(trace, facts, device):
    calls = trace.span_count.get("simulate_call")
    n = getattr(trace, "prog_count", {}).get("fleet.loop")
    if not calls or n is None:
        return None
    return n / calls
