"""executor_ms.offline: see bench/readers.py."""

from readers import executor_ms as read  # noqa: F401
