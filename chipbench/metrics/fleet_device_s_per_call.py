"""Device busy time inside each `simulate_call` span, averaged over the
calls of the window."""


def read(trace, facts, device):
    n = trace.span_count.get("simulate_call")
    if not n:
        return None
    return trace.span_busy_s["simulate_call"] / n
