"""TPU Pallas kernels for the paper's compute hot-spots: the error-corrected
single-precision GEMM itself (the paper's CUTLASS kernel, re-derived for the
bf16 MXU + VMEM memory hierarchy), the fused TCEC flash-attention kernel
built on the same split/term schedule, plus the dispatch + autotuning
subsystem that routes every eligible framework contraction through them."""
from .ops import pick_block, tcec_matmul
from .ref import matmul_f64, tcec_bmm_ref, tcec_matmul_ref
from .tcec_matmul import (EPILOGUE_ACTIVATIONS, VMEM_BUDGET,
                          tcec_matmul_pallas, vmem_bytes)
from .tcec_attention import (attn_vmem_bytes, tcec_attention,
                             tcec_attention_pallas)
from .tcec_paged_attention import (paged_vmem_bytes, tcec_paged_attention,
                                   tcec_paged_attention_pallas)
from .kv_page_write import kv_page_write, kv_page_write_pallas
from . import dispatch, shmap, tuning

__all__ = ["tcec_matmul", "pick_block", "tcec_matmul_ref", "tcec_bmm_ref",
           "matmul_f64", "tcec_matmul_pallas", "vmem_bytes", "VMEM_BUDGET",
           "EPILOGUE_ACTIVATIONS", "tcec_attention", "tcec_attention_pallas",
           "attn_vmem_bytes", "tcec_paged_attention",
           "tcec_paged_attention_pallas", "paged_vmem_bytes",
           "kv_page_write", "kv_page_write_pallas", "dispatch",
           "shmap", "tuning"]
