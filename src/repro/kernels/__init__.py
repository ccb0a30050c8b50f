# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Each kernel module exports ``kernel_structure()`` — the recovered
kstruct interior (loops / inlined scopes / source lines) that
``Profiler.register_kernel_structures`` binds to the kernel's
``custom-call`` HLO op for fine-grained PC-sample attribution."""

_KSTRUCT_CACHE = None


def kernel_structures():
    """Recover (and cache) the interior structures of all four Pallas
    kernels.  Tracing needs jax; callers on jax-less hosts should catch
    ImportError."""
    global _KSTRUCT_CACHE
    if _KSTRUCT_CACHE is None:
        from repro.kernels import (decode_attention, flash_attention,
                                   mla_decode, ssm_scan)
        _KSTRUCT_CACHE = (flash_attention.kernel_structure(),
                          decode_attention.kernel_structure(),
                          ssm_scan.kernel_structure(),
                          mla_decode.kernel_structure())
    return _KSTRUCT_CACHE
