"""Cache kernels (``kernels/cache_sim/``): device time of the Pallas
cache-simulation kernels' ops, as a share of the window."""

import re

# The kernels' custom calls as the TPU trace names them on the ``XLA Ops``
# line, e.g. ``%lru_hits_carry.1 = (...) custom-call(...),
# custom_call_target="tpu_custom_call"``.
KERNELS = re.compile(r"^%(lru_hits_carry|fused_levels_pallas)[.\d]* = .*tpu_custom_call")


def read(layers):
    if layers.device is None or not layers.device.ops:
        return None
    seconds = sum(layers.device.op_seconds(KERNELS).values())
    if seconds <= 0:
        return None
    return 100.0 * seconds / layers.device.window_s
