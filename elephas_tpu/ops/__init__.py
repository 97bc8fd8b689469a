from .attention import attention, blockwise_attention
from .pallas_attention import flash_attention
from .ring_attention import (ring_attention, ring_attention_sharded,
                             ring_flash_attention)
