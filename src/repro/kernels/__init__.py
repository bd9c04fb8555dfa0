from repro.kernels import (backend, flash_attention, paged, ref, rmsnorm,
                           ssd)

__all__ = ["backend", "flash_attention", "paged", "ref", "rmsnorm", "ssd"]
