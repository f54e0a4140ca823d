"""The ungated experts' grouped products' share of their roofline:
``flops_hybrid_moe.gmm_work`` (the expected rows of even routing, nothing for
unoccupied rows; as many forward products as the gauge
``hvd.moe.expert_products`` of the step's program counts, two an expert layer
without a gate and twice that for a recomputed one, and four backward an
expert layer of the configuration's ``pattern``) at the v5e's peaks, over the
time of the ``ragged-dot-none`` family (``moe_gmm_ms_per_step.tok``'s).
Nothing to read where that family is not among chip 0's ten largest or the
program sets no such gauge."""

from benchmarks import flops_hybrid_moe
from benchmarks.metrics import kernel_families as k
from benchmarks.metrics import program_spans


def read(record):
    ms = k.ms_per_step(record, k.GMM, k.GMM_ALSO)
    products = program_spans.step_gauge("hvd.moe.expert_products")
    if not ms or not products or record["peak"] is None:
        return None
    ops, nbytes = flops_hybrid_moe.gmm_work(
        fwd_products=products,
        tokens_per_step=record["window"]["units_per_step_per_chip"],
        **record["config"]["flops"]["args"],
        **record["cell"].get("flops_args", {}))
    least_s = max(ops / record["peak"]["bf16_flops_per_s"],
                  nbytes / record["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
