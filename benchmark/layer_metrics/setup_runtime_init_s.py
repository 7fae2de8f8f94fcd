"""setup_runtime_init_s — layer: replica set-up (the process's set-up
account, `ray_tpu/_private/telemetry.py`; `benchmark/setup_spans.py`).

Seconds of the `runtime.init` stage: the first call that needs a device,
so the TPU runtime's start in the replica's process. The slowest
replica's."""

from benchmark import engine_spans as es, setup_spans as su


@es.never_raises
def read(run: dict):
    return su.slowest(run, lambda acct: su.stage_s(acct, "runtime.init"))
