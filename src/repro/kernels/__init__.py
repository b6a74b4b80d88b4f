"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Each subpackage is <name>/{kernel.py (pl.pallas_call + BlockSpec),
ops.py (dispatching wrapper), ref.py (pure-jnp oracle)}:

    flash_attention   causal / sink+window (knob W) / block-sparse (knob
                      rho) / bidirectional AR-DiT attention
    paged_attention   chunk queries over the State Plane's head-major paged
                      KV pool (the serving executor's context backend),
                      and single-token decode (SS4.4)
    fp8_matmul        online-quantized scaled matmul (knob Q, SS6)
    ssd_scan          Mamba-2 SSD chunked scan (mamba2/jamba archs)

Kernels target TPU (MXU-aligned BlockSpecs, VMEM scratch carries) and are
validated on CPU in interpret mode against the oracles
(REPRO_FORCE_PALLAS_INTERPRET=1).  ``mode.kernel_mode`` picks the
implementation: on a TPU backend always the compiled kernel.
"""
