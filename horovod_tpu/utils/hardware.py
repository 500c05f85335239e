"""Per-chip hardware peaks, keyed by jax ``device_kind`` substring.

Used by the benchmarks to report MFU (model FLOPs utilization) and HBM
bandwidth pressure next to raw throughput, so a physically impossible
number is self-evident. Public
figures: TPU v4 275 TFLOPS bf16 / 1.23 TB/s; v5e 197 / 0.82; v5p 459 /
2.77; v6e (Trillium) 918 / 1.64.
"""

from __future__ import annotations

# Peak dense bf16 TFLOPS per chip.
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12,   # TPU v5e
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6 lite": 918e12,   # Trillium
    "v6e": 918e12,
}

# Peak HBM bandwidth per chip (bytes/s).
PEAK_HBM_BW = {
    "v5 lite": 819e9,    # TPU v5e
    "v5e": 819e9,
    "v4": 1228e9,
    "v5p": 2765e9,
    "v6 lite": 1640e9,   # Trillium
    "v6e": 1640e9,
}


def _by_device_kind(device, table) -> float:
    kind = getattr(device, "device_kind", "")
    for key, val in table.items():
        if key in kind.lower():
            return val
    platform = getattr(device, "platform", "cpu")
    if platform == "cpu":
        return 0.0  # no peaks for a host CPU -> callers report null
    # An accelerator the table does not know must not read as "no peak":
    # MFU and bandwidth shares would silently print null.
    raise ValueError(
        f"unknown device_kind {kind!r} on platform {platform!r}: add its "
        "peaks to horovod_tpu/utils/hardware.py")


def peak_flops(device) -> float:
    return _by_device_kind(device, PEAK_BF16_FLOPS)


def peak_hbm_bw(device) -> float:
    return _by_device_kind(device, PEAK_HBM_BW)


def scan_cost_analysis_steps(steps_per_call: int, unroll: int) -> int:
    """How many *steps* XLA's cost analysis counts for a
    ``lax.scan(body, length=steps_per_call, unroll=unroll)`` program.

    The while body is counted ONCE (verified on chip) and
    holds ``unroll`` steps; jax peels a remainder of
    ``steps_per_call % unroll`` steps outside the loop (also counted
    once). When ``unroll >= steps_per_call`` there is no while loop at
    all — the program is just ``steps_per_call`` peeled steps
    (jax _scan_impl: num_trips, remainder = divmod(length, unroll)).
    """
    spc = max(1, steps_per_call)
    if spc == 1:
        return 1  # no scan emitted by the callers in that case
    unroll = max(1, unroll)
    num_trips, remainder = divmod(spc, unroll)
    if num_trips == 0:
        return remainder
    return unroll + remainder
