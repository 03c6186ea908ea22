from . import compile_cache, fault_tolerance, shardings

__all__ = ["compile_cache", "fault_tolerance", "shardings"]
