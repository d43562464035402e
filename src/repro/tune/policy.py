"""Dispatch policies.

``"reference"`` - plain jnp, the oracle path.
``"model"``     - Pallas kernel, analytically planned config.
``"tuned"``     - Pallas kernel, measured config from the registry; cold
                  start falls back to the ``model`` resolution.

The policy of a call comes from the :mod:`repro.linalg` ExecutionContext,
which resolves it into each core's ``policy=`` argument.
"""
from __future__ import annotations

from typing import Optional

POLICIES = ("reference", "model", "tuned")

# policies whose execution path is the Pallas kernel
KERNEL_POLICIES = ("model", "tuned")


def default_policy() -> str:
    """The policy of a call that names none: ``"reference"``, the
    conservative oracle path. ``repro.linalg.set_context(policy=...)``
    changes the process default."""
    return "reference"


def resolve_policy(policy: Optional[str] = None) -> str:
    """Validate ``policy``; ``None`` takes :func:`default_policy`."""
    if policy is None:
        return default_policy()
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; expected one of "
                         f"{POLICIES}")
    return policy


def uses_kernel(policy: str) -> bool:
    return policy in KERNEL_POLICIES
