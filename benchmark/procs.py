"""Child processes of a run: started with the checkout on their path,
stopped and waited for when the run ends, whatever happened."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class Children:
    def __init__(self):
        self._procs: list[subprocess.Popen] = []

    def start(self, script: str, *argv: str, env: dict | None = None,
              ) -> subprocess.Popen:
        """``benchmark/<script>`` as a child with pipes on stdin and
        stdout. Children never get the chip: JAX in them, if anything
        imports it, is held to the CPU."""
        child_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                     "PYTHONPATH": os.pathsep.join(
                         [str(ROOT), os.environ.get("PYTHONPATH", "")]),
                     **(env or {})}
        p = subprocess.Popen(
            [sys.executable, str(ROOT / "benchmark" / script), *argv],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=child_env,
            cwd=str(ROOT), text=True)
        self._procs.append(p)
        return p

    @staticmethod
    def read_json(p: subprocess.Popen) -> dict:
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"child {p.args[1]} exited "
                               f"({p.poll()}) without an answer")
        return json.loads(line)

    def stop(self) -> None:
        for p in self._procs:
            if p.poll() is None:
                try:
                    p.stdin.close()  # both children exit at EOF
                except OSError:
                    pass
        for p in self._procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        self._procs.clear()
