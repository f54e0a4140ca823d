"""What device the process is on, said out loud.

JAX falls back to the CPU with a warning when libtpu finds no chip, and
a Pallas kernel asked to ``interpret`` runs anywhere. Both are right for
a library and wrong for a measurement: a number taken on the CPU must
never be read as the chip's. The measuring entry points
(``tools/profile_step.py``, ``tools/serve_bench.py``,
``tools/decode_bench.py``, ``chip_smoke.py``; ``benchmarks/run.py`` has
its own look for the chip) go through :func:`require_tpu`; the kernels through
:func:`pallas_interpret`.
"""

from __future__ import annotations

from typing import Dict


def device_stamp() -> Dict:
    """``{"platform", "device_kind", "count"}`` as JAX reports them —
    stamped into every record a measuring entry point prints."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(cpu_requested: bool = False) -> Dict:
    """The device stamp, or ``SystemExit`` when the platform is not
    ``tpu``. ``cpu_requested`` is the caller's existing explicit test
    switch (``HVD_TPU_FORCE_CPU`` for ``tools/profile_step.py``,
    ``JAX_PLATFORMS=cpu`` for the serving tools): only then may a CPU run proceed."""
    stamp = device_stamp()
    if stamp["platform"] == "tpu":
        return stamp
    if cpu_requested and stamp["platform"] == "cpu":
        return stamp
    raise SystemExit(
        f"no TPU: JAX reports platform={stamp['platform']!r} "
        f"device_kind={stamp['device_kind']!r} count={stamp['count']} — "
        "this entry point measures the chip and does not fall back to "
        "another platform")


def memory_limit():
    """Bytes of memory a device of this process offers, where the backend
    says (a TPU: ``bytes_limit``); None where it does not (the CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def pallas_interpret() -> bool:
    """Default for a kernel's ``interpret`` argument: compiled on a TPU,
    interpreted on the CPU test platform, an error anywhere else."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels are compiled by Mosaic on a TPU and interpreted "
        f"only on the CPU test platform; default backend is {backend!r}")
