"""Host time per fleet call outside the engine's device loop: the
`simulate_call` span less the `fleet.loop` entries inside it (draws,
tables, pools, transfers, results), per call. A call's device work all
lies inside `fleet.loop`, so the chip has nothing to run meanwhile."""


def read(trace, facts, device):
    calls = trace.span_count.get("simulate_call")
    loop = getattr(trace, "prog_host_s", {}).get("fleet.loop")
    if not calls or loop is None:
        return None
    return 1e3 * (trace.span_host_s["simulate_call"] - loop) / calls
