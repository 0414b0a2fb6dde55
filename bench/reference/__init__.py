"""Plain reference of the served models, independent of the program."""
from .transformer import logits

__all__ = ["logits"]
