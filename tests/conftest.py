"""Skip jax-dependent test modules when jax is unavailable.

CI installs only the ``dev`` extras; the AFT core, faas, and workflow
suites are framework-free and run everywhere, while the model/serving/
checkpoint/kernel suites need the ``jax`` extra.
"""

import importlib.util

collect_ignore = []
if importlib.util.find_spec("jax") is None:
    collect_ignore = [
        "test_arch_smoke.py",
        "test_checkpoint.py",
        "test_chip_smoke.py",
        "test_kernels.py",
        "test_models_blocks.py",
        "test_property_ckpt.py",
        "test_serve_continuous.py",
        "test_serve_lane.py",
        "test_trainer_serve.py",
        "test_tpu_compile.py",
    ]
