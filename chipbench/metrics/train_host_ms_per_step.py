"""Host time per train step in which the chip has nothing queued: the
`train.step` span less the `train.sync` wait inside it (the batch, the
dispatch and the observers), per step. Nothing where the reduction has
no program spans."""


def read(trace, facts, device):
    host = getattr(trace, "prog_host_s", {})
    steps = getattr(trace, "prog_count", {}).get("train.step")
    if not steps or "train.sync" not in host:
        return None
    return 1e3 * (host["train.step"] - host["train.sync"]) / steps
