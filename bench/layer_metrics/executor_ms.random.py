"""executor_ms.random: see bench/readers.py."""

from readers import executor_ms as read  # noqa: F401
