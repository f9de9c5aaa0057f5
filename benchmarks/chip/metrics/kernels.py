"""Which device operations are the SGNS kernel: the Mosaic custom calls
of the HBM pipeline (``_hbm_pipe_kernel``), named by the lowering."""

KERNEL_MARKERS = ("hbm_pipe", "tpu_custom_call", "mosaic")


def is_sgns_kernel(op_name: str) -> bool:
    name = op_name.lower()
    return any(m in name for m in KERNEL_MARKERS)
