"""oms_overscan.open: see bench/readers.py."""

from readers import oms_overscan as read  # noqa: F401
