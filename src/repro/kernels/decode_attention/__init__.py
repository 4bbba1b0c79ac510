from repro.kernels.decode_attention.ops import (decode_attention, live_slots,
                                                uses_kernel)
