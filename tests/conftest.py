import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# tests/chaos.py holds real test functions but is imported (via
# tests/test_chaos.py) rather than collected directly; opt it into
# pytest's assert rewriting so its failures stay introspectable
pytest.register_assert_rewrite("chaos")

import jax

jax.config.update("jax_platform_name", "cpu")
