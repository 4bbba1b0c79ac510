from repro.kernels.encoder_attention.ops import encoder_attention, uses_kernel
