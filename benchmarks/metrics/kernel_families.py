"""What the readers of a kernel's time and roofline share have in common.

``run.py`` removes the profile before the readers run, so a kernel's time is
read from ``record["trace"]["device_ops"]``: chip 0's ten largest operation
families of the traced steps, each ``[family, seconds]`` (self time; the
family is the instruction's name less its number, which for a kernel is the
kernel's own name). A family that is not among the ten cannot be read: the
reader then returns ``None`` and the metric is left out of the line, as it is
on a program that does not run the kernel at all.

The grouped products of the expert layers are XLA:TPU's own lowering of
``jax.lax.ragged_dot``: one ``ragged-dot-none`` instruction a product (its
small ``ragged-dot-metadata`` companion is counted where it is listed). The
attention kernels are the program's Pallas kernels ``hvd_flash_fwd``,
``hvd_flash_dq`` and ``hvd_flash_dkv`` (``horovod_tpu/ops/attention.py``).
"""

GMM = ("ragged-dot-none",)
GMM_ALSO = ("ragged-dot-metadata",)
FLASH = ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv")


def ms_per_step(record, needed, also=()):
    """Milliseconds a step of the families ``needed`` (every one of them has
    to be among the ten) plus those of ``also`` that are listed."""
    t = record["trace"]
    if t is None or not t.get("steps"):
        return None
    listed = dict(map(tuple, t["device_ops"]))
    if any(name not in listed for name in needed):
        return None
    seconds = sum(listed.get(name, 0.0) for name in tuple(needed) + tuple(also))
    return 1e3 * seconds / t["steps"]


def roofline_pct(record, needed, also, work: str):
    """The least time the chip could take for the kernel's work (the larger
    of operations over the peak and bytes over the memory's rate, from
    ``flops_moe.py`` through the configuration's ``flops`` arguments) over
    the time it took."""
    ms = ms_per_step(record, needed, also)
    if not ms or record["peak"] is None:
        return None
    from benchmarks import flops_moe

    ops, nbytes = getattr(flops_moe, work)(
        tokens_per_step=record["window"]["units_per_step_per_chip"],
        **record["config"]["flops"]["args"],
        **record["cell"].get("flops_args", {}))
    least_s = max(ops / record["peak"]["bf16_flops_per_s"],
                  nbytes / record["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
