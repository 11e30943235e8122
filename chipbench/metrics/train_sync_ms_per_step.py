"""Host time per train step spent waiting on the device for the step's
loss (`train.sync`), per `train.step`."""


def read(trace, facts, device):
    host = getattr(trace, "prog_host_s", {})
    steps = getattr(trace, "prog_count", {}).get("train.step")
    if not steps or "train.sync" not in host:
        return None
    return 1e3 * host["train.sync"] / steps
