"""Share of device busy time in the decode-attention kernels
(ops/pallas/decode_attention.py).  The kernels carry no name of their own
yet: in the trace each is ``%_step_fn.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"``, and they are the only Mosaic calls of
the serving step, so the call target is what is matched (a stable kernel
``name`` is the tracing issue's)."""
from benchmark import trace_reduce

KERNELS = r'custom_call_target="tpu_custom_call"'


def read(obs):
    t = obs.get("trace")
    if not t or "requests" not in obs:
        return None
    return 100.0 * trace_reduce.ops_seconds(t, KERNELS) / t["busy_s"]
