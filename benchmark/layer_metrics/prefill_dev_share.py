"""prefill_dev_share — layer: model step (models/transformer.py through
`jit_prefill`, `jit_place`, `jit_sample1`).

Device time of the admission programs' executions over the device's busy
time in the traced window, in %. Prefill takes the device from decode, so
this is the share of device time that the requests already decoding wait
for."""

from benchmark import spans as sp

PROGRAMS = ("jit_prefill", "jit_place", "jit_sample1")


def read(run: dict):
    secs = sp.program_seconds(run, PROGRAMS)
    if secs is None:
        return None
    devs = run["profile"]["devices"]
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    return 100.0 * secs / busy if busy > 0 else None
