"""The grouped scan kernels' share of their roofline: ``flops_hybrid_moe.
ssd_work`` (their products as the kernels run them, ``G = C B^T`` made once a
chunk a group, over the forward calls the step executes, recomputed ones
included, and one backward call a Mamba layer of the configuration's
``pattern``) at the v5e's peaks, over the time of the family ``hvd_ssd_scan``.
The forward calls, the chunk and the groups are the gauges
``hvd.ssd.fwd_calls``, ``hvd.ssd.chunk`` and ``hvd.ssd.groups`` of the step's
program. Nothing to read where that family is not among chip 0's ten largest
or the program sets no such gauge (a program whose scan knows no groups)."""

from benchmarks import flops_hybrid_moe
from benchmarks.metrics import kernel_families as k
from benchmarks.metrics import program_spans


def read(record):
    ms = k.ms_per_step(record, flops_hybrid_moe.SSD_KERNELS)
    gauges = {name: program_spans.step_gauge("hvd.ssd." + name)
              for name in ("fwd_calls", "chunk", "groups")}
    if not ms or not all(gauges.values()) or record["peak"] is None:
        return None
    sizes = dict(record["config"]["flops"]["args"],
                 **record["cell"].get("flops_args", {}))
    sizes.update(chunk=gauges["chunk"], ssm_groups=gauges["groups"])
    ops, nbytes = flops_hybrid_moe.ssd_work(
        fwd_calls=gauges["fwd_calls"],
        bwd_calls=sizes["pattern"].count(flops_hybrid_moe.MAMBA),
        tokens_per_step=record["window"]["units_per_step_per_chip"], **sizes)
    least_s = max(ops / record["peak"]["bf16_flops_per_s"],
                  nbytes / record["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
