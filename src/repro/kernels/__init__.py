"""Pallas TPU kernels for the compute hot-spots (matmul / flash attention /
selective scan).

Also the ONE place that maps the mapper's R-axis bit-widths onto executable
kernel dtypes (``kernel_bits`` / ``dtype_for_bits``) — defined here, not in
``repro.core``, so the core->kernels dependency stays one-way (the genome
bridge in ``repro.core.kernel_bridge`` imports this package, never the
reverse).
"""

# Widths each kernel's datapath can execute.  Sub-byte mapper widths (the
# R axis offers 2/4-bit) execute at the narrowest supported container — the
# cost model still credits the sub-byte storage/bandwidth, the silicon just
# computes at byte granularity.  Attention and the selective scan keep f32
# state (online softmax / recurrent exp), so their floors are wider.
SUPPORTED_BITS = {
    "matmul": (8, 16, 32),
    "attention": (16, 32),
    "mamba": (32,),
}


def kernel_bits(bits: int, kind: str = "matmul") -> int:
    """Executed operand width for a requested R-axis width: the smallest
    supported width >= ``bits``, saturating at the widest supported."""
    menu = SUPPORTED_BITS[kind]
    for b in menu:
        if bits <= b:
            return b
    return menu[-1]


def dtype_for_bits(bits: int, kind: str = "matmul"):
    """The jnp dtype a kernel executes a requested R-axis width at
    (8 -> int8 quantized, 16 -> bfloat16, 32 -> float32)."""
    import jax.numpy as jnp
    return {8: jnp.int8, 16: jnp.bfloat16,
            32: jnp.float32}[kernel_bits(bits, kind)]

