"""Host time per fleet call drawing randomness: the initial lifetimes
(`fleet.draws`) and the replacement pools with their chaos join uniforms
(`fleet.pools`), per `simulate_call`."""


def read(trace, facts, device):
    calls = trace.span_count.get("simulate_call")
    host = getattr(trace, "prog_host_s", {})
    if not calls or "fleet.draws" not in host:
        return None
    return 1e3 * (host["fleet.draws"] + host.get("fleet.pools", 0.0)) / calls
