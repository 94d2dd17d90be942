"""Plain reference of the benchmark's configurations.

It imports nothing of the program: its text helpers, filters and language
model are written out here, and the data they need (language profiles, the
tokenizer the deployment resolves ``gpt2`` to) is copied under ``data/``.
"""

from .filters import Reference

__all__ = ["Reference"]
