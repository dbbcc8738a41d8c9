"""A kernel's share of its roofline, as ``kernel_roofline`` reads it (the
least time the chip could take for the operations and bytes the kernel
requires, over the device time of the kernel's scopes in ``passes``, in %),
for the kernels of ``benchmark/kernels_phi4flash.py``, whose work is fixed by
the cell's shapes (every position of every step). A program without the scopes
(a checkout from before they were written) gives nothing to read."""
from benchmark import device, kernels_phi4flash
from benchmark.readers import trace_scope_lm


def read(result, kernel, scopes, shape, passes=None):
    ms = trace_scope_lm.read(result, scopes=scopes, passes=passes)
    if not ms or result["device"]["platform"] != "tpu":
        return None
    need = getattr(kernels_phi4flash, kernel)(**shape)
    peak = device.peaks(result["device"]["kind"])
    least_s = max(need["flops"] / peak["bf16_flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
