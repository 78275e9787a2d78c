"""device_idle.open: see bench/readers.py."""

from readers import device_idle as read  # noqa: F401
