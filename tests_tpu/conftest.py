"""On-device (real TPU) test tier.

Unlike ``tests/`` (which forces an 8-device virtual CPU mesh), this suite
runs on whatever accelerator JAX finds and fails the whole session when that
is not a TPU: a chip call that landed on the CPU must not come back green.
Run explicitly: ``python -m pytest tests_tpu/ -q`` — it is NOT in
pyproject's default testpaths, because CI sandboxes have no chip.
"""

import jax
import pytest


def pytest_sessionstart(session):
    backend = jax.default_backend()
    if backend != "tpu":
        pytest.exit(
            f"tests_tpu needs a TPU backend, jax found {backend!r} "
            f"({jax.devices()[0].device_kind}); run it on the chip without JAX_PLATFORMS",
            returncode=1,
        )
