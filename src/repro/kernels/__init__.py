"""Pallas kernels for the MiniConv shader-pass schedule.

Module map
----------
``miniconv_pass``
    The execution tiers behind the ``repro.core.backends`` registry:
    :func:`~repro.kernels.miniconv_pass.miniconv_pass` (per-pass oracle,
    backend ``reference``), :func:`~repro.kernels.miniconv_pass.
    miniconv_layer_grouped` (``grouped``), :func:`~repro.kernels.
    miniconv_pass.miniconv_encoder` (``fused`` / ``fused+head`` — the
    whole encoder, optionally with the projection epilogue, as ONE
    pallas_call) and :func:`~repro.kernels.miniconv_pass.
    miniconv_encoder_stream` (``fused+stream`` — the fused kernel
    pipelined over batch chunks, lifting the batch-must-fit-VMEM cap).
``ops``
    Public jit'd wrappers (``miniconv_layer``) used by the per-pass and
    grouped tiers.
``ref``
    Pure-jnp oracles every kernel here is parity-tested against.
``flash_attention``
    Blocked (flash) attention prefill kernel for the baselines.
``interpret``
    ``resolve_interpret``, the one interpret/compile switch: kernels are
    interpreted on the CPU backend (or when a caller passes
    ``interpret=True``) and compiled everywhere else.
"""
