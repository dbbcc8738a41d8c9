"""A kernel's share of its roofline, as ``kernel_roofline`` reads it (the
least time the chip could take for the operations and bytes the kernel
requires, over the device time of the kernel's scopes in ``passes``, in %),
for the kernels of ``benchmark/kernels_ssm.py``. ``rows`` names the
program's histogram that counts the kernel's rows (its median over the
window); a kernel whose work is fixed by the cell's shapes (the scan: every
position of every step) has none and takes everything from ``shape``.
A program without the scopes (a checkout from before they were written)
gives nothing to read."""
from benchmark import device, kernels_ssm
from benchmark.readers import histogram_window, trace_scope_lm


def read(result, kernel, scopes, shape, rows=None, passes=None):
    ms = trace_scope_lm.read(result, scopes=scopes, passes=passes)
    n = histogram_window.read(result, **rows) if rows else None
    if not ms or (rows and n is None) or result["device"]["platform"] != "tpu":
        return None
    need = getattr(kernels_ssm, kernel)(*(() if n is None else (n,)), **shape)
    peak = device.peaks(result["device"]["kind"])
    least_s = max(need["flops"] / peak["bf16_flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
