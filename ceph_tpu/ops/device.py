"""Which device the data plane runs on — asked in ONE place.

Every kernel has two formulations: the Pallas/Mosaic one the chip
runs, and an XLA twin that exists so the tests can run on CPU.  Which
of the two a process uses is decided here, once, from the platform JAX
reports, and is visible: `describe()` rides the OSD's `prewarm status`
asok, the benchmark tools' output and chip_smoke.py's JSON, and every
fused drain records its kernel path (`fused_path`, the
ec_fused_kernel_drains / ec_fused_fallback_drains counters).

JAX itself falls back to CPU with a warning when the TPU runtime
fails to initialise and JAX_PLATFORMS is unset.  Nothing here papers
over that: a process that was meant to have a chip and got CPU reports
platform "cpu" and kernels "xla-twin", and the tools that measure
(bench.py, ec_benchmark -p jax, chip_smoke.py) refuse to run on it.
"""

from __future__ import annotations

import functools

# Published per-chip peaks, keyed by the `device_kind` string JAX
# reports.  A device that is not in the table is an error, not a
# default: a roofline share against the wrong peak is a wrong number.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                    "bf16_flops_per_s": 197e12,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


@functools.cache
def describe() -> dict:
    """Platform facts of this process as JAX reports them, plus the
    kernel family they select.  Initialises the JAX backend."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "kernels": "xla-twin" if dev.platform == "cpu"
            else "pallas-mosaic"}


def on_cpu() -> bool:
    """True when the kernels must run as their XLA twins (CPU tests);
    False on an accelerator, where they compile through Mosaic."""
    return describe()["platform"] == "cpu"


def require_accelerator(what: str) -> dict:
    """For tools whose numbers only mean something on a chip: returns
    describe(), or exits with one clear line when JAX found none."""
    info = describe()
    if info["platform"] == "cpu":
        raise SystemExit(
            f"{what}: JAX found no accelerator (platform=cpu, "
            f"kind={info['kind']!r}); refusing to run on the CPU twin")
    return info


def peaks() -> dict:
    """Published peaks of the device this process runs on."""
    kind = describe()["kind"]
    try:
        return PEAKS[kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"ceph_tpu/ops/device.py PEAKS with its source") from None
