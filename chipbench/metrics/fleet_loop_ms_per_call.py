"""Host time per fleet call inside the engine's `lax.while_loop` entries
(`fleet.loop`: dispatch, the device loop and the wait for its masks),
per `simulate_call`."""


def read(trace, facts, device):
    calls = trace.span_count.get("simulate_call")
    loop = getattr(trace, "prog_host_s", {}).get("fleet.loop")
    if not calls or loop is None:
        return None
    return 1e3 * loop / calls
