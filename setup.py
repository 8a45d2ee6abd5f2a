"""Build glue: compile the native host data plane at install time.

The C++ data plane (analytics_zoo_tpu/native/dataplane.cpp — ring buffer,
parallel CSV, ZREC store) is a plain shared library bound via ctypes, not a
Python extension module, so it is built with a custom command rather than
Extension().  If no C++ toolchain exists at install time, the build is
skipped and the library compiles lazily on first use instead
(native.load_lib); pure-Python paths keep working either way.
"""

import hashlib
import subprocess
import sys
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        super().run()
        src = Path(__file__).parent / "analytics_zoo_tpu" / "native" / \
            "dataplane.cpp"
        # the loader (native/__init__.py) looks for the binary under the
        # source's content hash, so a pre-built one must carry it too
        digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
        for base in [Path(self.build_lib), Path(__file__).parent]:
            out = base / "analytics_zoo_tpu" / "native" / \
                f"libzoo_dataplane.{digest}.so"
            if not out.parent.exists():
                continue
            cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                   "-pthread", str(src), "-o", str(out)]
            try:
                subprocess.run(cmd, check=True, capture_output=True)
                print(f"built native data plane -> {out}")
            except (FileNotFoundError, subprocess.CalledProcessError) as e:
                print(f"warning: native build skipped ({e}); will compile "
                      "lazily on first use", file=sys.stderr)
            break


setup(cmdclass={"build_py": BuildWithNative})
