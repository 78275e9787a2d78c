"""batch_fill.open: see bench/readers.py."""

from readers import batch_fill as read  # noqa: F401
