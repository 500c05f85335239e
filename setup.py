"""Packaging with native-extension build (reference: setup.py — 770 lines
of MPI/CUDA/NCCL feature detection; here the native engine needs only a
C++17 toolchain, so the build reduces to one g++ invocation).

    pip install .           # builds libhvdcore.<key>.so into the wheel
    HVD_SKIP_NATIVE=1 pip install .   # python-engine-only install
"""

import os

from setuptools import Command, find_packages, setup
from setuptools.command.build_py import build_py


class BuildNative(Command):
    """Compile libhvdcore next to its source (the runtime also builds
    on demand, so failure here degrades to the python engine rather than
    failing the install — the reference instead hard-fails without MPI)."""

    description = "build the native engine"
    user_options = []

    def initialize_options(self):  # noqa: D102
        pass

    def finalize_options(self):  # noqa: D102
        pass

    def run(self):  # noqa: D102
        if os.environ.get("HVD_SKIP_NATIVE"):
            return
        # One build recipe, the runtime loader's: it names the library
        # after a hash of source + flags, so what the wheel ships is
        # what the loader accepts. Loaded by file path — importing the
        # package would pull in jax.
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "_hvd_native", os.path.join("horovod_tpu", "core", "native",
                                        "__init__.py"))
        native = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(native)
        try:
            native.build_library()
        except (OSError, native.NativeBuildError) as e:
            print(f"WARNING: native engine build failed ({e}); "
                  "the python engine will be used (HVD_ENGINE=python)")


class BuildPy(build_py):
    def run(self):
        self.run_command("build_native")
        super().run()


setup(
    name="horovod_tpu",
    version="0.1.0",
    description="TPU-native distributed training framework "
                "(Horovod-capability parity on JAX/XLA)",
    packages=find_packages(include=["horovod_tpu", "horovod_tpu.*"]),
    package_data={"horovod_tpu.core.native": ["*.so", "*.cc"]},
    python_requires=">=3.10",
    install_requires=["jax", "flax", "optax", "numpy", "scipy"],
    extras_require={
        "torch": ["torch"],
        "tensorflow": ["tensorflow"],
        "haiku": ["dm-haiku"],
    },
    cmdclass={"build_native": BuildNative, "build_py": BuildPy},
)
