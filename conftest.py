"""Root conftest: the tests run on the CPU, on 8 virtual devices.

The full PS protocol runs single-process on a fake mesh (SURVEY.md section
4 implication). jax reads the platform and the virtual device count once,
when the backend is created, so they go into the environment here, before
any test module can import jax — and before any child process a test
spawns inherits it. A chip, if the machine has one, is left alone.
"""

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO_ROOT)

from tpu_env import clean_cpu_env  # noqa: E402 (stdlib-only)

os.environ.update(clean_cpu_env())
