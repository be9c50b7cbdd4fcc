"""Hard transfer enforcement: the runtime half of lint rule R1.

It lives beside ``obs`` and ``utils/compile_cache.py`` (it needs ``jax`` and
``obs`` only), so the measured path imports no development tooling;
``photon_ml_tpu.analysis`` re-exports the four public names.

The static linter (R1) catches implicit device->host syncs it can see in the
source; :func:`transfer_guard` catches the ones it cannot — attribute-chained
values, third-party calls, future regressions. Inside the guard JAX raises
on any *implicit* device->host transfer (``float(arr)``, ``np.asarray(arr)``,
iterating an array, ...), while explicit ``jax.device_get`` stays allowed.
The convention, enforced end to end:

- hot loops (the CD sweep, the benchmark's fits) run inside
  ``transfer_guard()``;
- every legitimate fetch goes through :func:`logged_fetch`, which is
  explicit (guard-proof) AND counted in the obs registry
  (``photon_device_fetch_bytes_total{site=...}``, and the seconds it blocked
  in ``photon_device_fetch_seconds_total{site=...}``).

Together they promote PR 1's zero-fetch invariant from "a test asserts the
tracker was lazy" to "the runtime hard-errors on any unlogged fetch".

``PHOTON_TRANSFER_GUARD`` overrides the guard level globally: ``off``
disables it (escape hatch for debugging), ``log`` demotes errors to logged
warnings, ``disallow`` (default) raises.

Enforcement is an XLA-runtime property: on accelerator backends (TPU, GPU)
a device->host copy is a real DMA and the guard intercepts it; on the CPU
backend device buffers alias host memory, the "transfer" is zero-copy, and
XLA never routes it through the guard — ``disallow`` there is a no-op.
:func:`guard_level` exposes the innermost active level so callers (and
tests on any backend) can observe the guard state itself.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator

import jax

from .. import obs

_LEVELS = ("off", "allow", "log", "disallow")

# innermost-first stack of active guard levels; list ops are atomic under the
# GIL and the guard is only meaningful per-thread anyway (jax's own guard
# state is thread-local)
_active: list = []


def guard_level() -> str | None:
    """The innermost active guard level, or None outside any guard."""
    return _active[-1] if _active else None


def _guard_level(level: str) -> str:
    env = os.environ.get("PHOTON_TRANSFER_GUARD", "").strip().lower()
    if env:
        if env not in _LEVELS:
            raise ValueError(
                f"PHOTON_TRANSFER_GUARD={env!r}: expected one of {_LEVELS}"
            )
        return "allow" if env == "off" else env
    return level


@contextlib.contextmanager
def transfer_guard(level: str = "disallow") -> Iterator[None]:
    """Hard-error (or log) on implicit device->host fetches in the block.

    Only the device->host direction is guarded: host->device staging (numpy
    inputs to jit, ``jax.device_put``) is how data is SUPPOSED to flow and
    stays unrestricted. Explicit fetches (``jax.device_get``, i.e.
    :func:`logged_fetch`) remain allowed — the point is that every fetch in
    a guarded region is deliberate and counted, not that there are none."""
    effective = _guard_level(level)
    with jax.transfer_guard_device_to_host(effective):
        _active.append(effective)
        try:
            yield
        finally:
            _active.pop()


@contextlib.contextmanager
def allow_transfers() -> Iterator[None]:
    """Locally lift :func:`transfer_guard` — for host-bound excursions like
    checkpoint writes inside a guarded loop. Keep the block small; anything
    long-lived should instead fetch through :func:`logged_fetch`."""
    with jax.transfer_guard_device_to_host("allow"):
        _active.append("allow")
        try:
            yield
        finally:
            _active.pop()


def _leaf_nbytes(x) -> int:
    nbytes = getattr(x, "nbytes", None)
    return int(nbytes) if nbytes is not None else 0


def logged_fetch(site: str, tree):
    """Explicit, counted device->host fetch of an array or pytree.

    Returns host numpy (``jax.device_get``); numpy inputs pass through
    unchanged and are not counted. ``site`` labels the transfer in
    ``photon_device_fetch_bytes_total`` so a sweep's fetch budget is
    attributable line-item by line-item, and in
    ``photon_device_fetch_seconds_total``: the call blocks until the device
    has produced ``tree``, so with no sink attached (no fence drains the
    device first) the seconds over all sites against the job's wall are the
    share of time the host stood waiting for the chip."""
    import numpy as np

    nbytes = sum(
        _leaf_nbytes(leaf)
        for leaf in jax.tree_util.tree_leaves(tree)
        if not isinstance(leaf, (np.ndarray, np.generic))
    )
    start = time.perf_counter()
    host = jax.device_get(tree)
    end = time.perf_counter()
    if nbytes:
        obs.record_device_fetch(site, nbytes, start, end)
    return host
