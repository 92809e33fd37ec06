"""Host time per step: from the previous step's loss sync to the return
of this step's dispatch (batch to the device, step arguments, call)."""


def read(r):
    return sum(r.host_ms) / len(r.host_ms) if r.host_ms else None
