"""The attention kernels' share of their roofline where keys and values differ
in width (a latent layer's 192 and 128): ``flops_mla.attn_work`` (seven
products a visible pair a head, four of them as wide as a key and three as a
value, under the causal mask, recomputation not counted) at the v5e's peaks,
over the time of the three ``hvd_flash_*`` families
(``kernel_families.ms_per_step``: all three have to be among chip 0's ten
largest). ``flash_roofline_pct.tok``'s work function has one width."""

from benchmarks.metrics import kernel_families as k


def read(record):
    ms = k.ms_per_step(record, k.FLASH)
    if not ms or record["peak"] is None:
        return None
    from benchmarks import flops_mla

    ops, nbytes = flops_mla.attn_work(
        tokens_per_step=record["window"]["units_per_step_per_chip"],
        **record["config"]["flops"]["args"],
        **record["cell"].get("flops_args", {}))
    least_s = max(ops / record["peak"]["bf16_flops_per_s"],
                  nbytes / record["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
