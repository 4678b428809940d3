"""Device milliseconds per step of the f16 bucket pack and unpack kernels
(``kernels/bucket_ops.py``, which ``distributed/bucketing.py:_kernel_on``
selects on TPU), from the trace.

The kernels carry no name of their own in the trace yet: they are the
``tpu_custom_call`` operations that turn an f32 (rows, 128) stream into
u16 (the f16 bits) or back."""
import re

from bench import traces

_PACK = re.compile(r"= u16\[(\d+),128\]\S* custom-call\(f32\[\1,128\]")
_UNPACK = re.compile(r"= f32\[(\d+),128\]\S* custom-call\(u16\[\1,128\]")


def is_cast_kernel(name):
    return ('custom_call_target="tpu_custom_call"' in name
            and bool(_PACK.search(name) or _UNPACK.search(name)))


def read(ctx):
    if ctx.trace is None or ctx.traced_steps <= 0:
        return None
    t = traces.op_seconds(ctx.trace, is_cast_kernel)
    return None if t is None else 1e3 * t / ctx.traced_steps
