"""The scan kernels' share of their roofline: ``flops_ssm.ssd_work`` (their
products as the kernels run them, at a head's 64 columns, over the forward
calls the step executes, recomputed ones included, which the gauge
``hvd.ssd.fwd_calls`` of the step's program counts, and one backward call a
Mamba layer) at the v5e's peaks, over the time of the family
``hvd_ssd_scan`` (``ssd_ms_per_step.tok``'s). Nothing to read where that
family is not among chip 0's ten largest or the program sets no such
gauge."""

from benchmarks import flops_ssm
from benchmarks.metrics import kernel_families as k
from benchmarks.metrics import program_spans


def read(record):
    ms = k.ms_per_step(record, flops_ssm.KERNELS)
    fwd_calls = program_spans.step_gauge("hvd.ssd.fwd_calls")
    if not ms or not fwd_calls or record["peak"] is None:
        return None
    sizes = dict(record["config"]["flops"]["args"],
                 **record["cell"].get("flops_args", {}))
    ops, nbytes = flops_ssm.ssd_work(
        fwd_calls=fwd_calls,
        bwd_calls=sum(t == flops_ssm.MAMBA for t in sizes["layer_types"]),
        tokens_per_step=record["window"]["units_per_step_per_chip"], **sizes)
    least_s = max(ops / record["peak"]["bf16_flops_per_s"],
                  nbytes / record["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
