"""Device busy time inside the `train_call` spans, per step they ran."""


def read(trace, facts, device):
    steps = facts.get("train_call_steps")
    busy = trace.span_busy_s.get("train_call")
    if not steps or busy is None:
        return None
    return 1e3 * busy / steps
