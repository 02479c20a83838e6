from repro.utils.struct import pytree_dataclass, static_field

__all__ = ["pytree_dataclass", "static_field"]
