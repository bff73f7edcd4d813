"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax initializes.

Unit tests run on a deterministic 8-way CPU topology whatever accelerator
the machine has — the TPU analog of the reference's "two instances on one
LanceDB dir" cross-process tests (SURVEY §4(e)).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import pytest  # noqa: E402


@pytest.fixture()
def tmp_db(tmp_path):
    return str(tmp_path / "db")
