"""The toolchain ledger must mirror the conformance skip conditions."""

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    """Import a scripts/*.py module hermetically (no cwd / sys.path
    dependence — scripts/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        f"_r5_{name}", os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_toolchain_probe_mirrors_conformance_gate(monkeypatch):
    """java_lane_runnable must equal the test gate's condition (javac AND
    java on PATH), so the ledger never misattributes a skip.  Every
    scenario stubs _run and the bazel-JRE glob so no subprocess spawns
    and no host state leaks in."""
    tp = _load_script("toolchain_probe")
    monkeypatch.setattr(
        tp, "_run", lambda cmd, timeout=30: (0, "openjdk 21\njava.base"))
    monkeypatch.setattr(tp.glob, "glob", lambda pat: [])

    def which(names):
        return lambda exe: f"/usr/bin/{exe}" if exe in names else None

    monkeypatch.setattr(tp.shutil, "which", which({"javac"}))
    doc = tp.probe()
    assert doc["java_lane_runnable"] is False
    assert "java" in doc["conformance_expected_skips"]

    monkeypatch.setattr(tp.shutil, "which", which({"javac", "java"}))
    doc = tp.probe()
    assert doc["java_lane_runnable"] is True
    assert "java" not in doc["conformance_expected_skips"]

    monkeypatch.setattr(tp.shutil, "which", which({"Rscript", "R"}))
    doc = tp.probe()
    assert doc["r_lane_runnable"] is True
    assert "r" not in doc["conformance_expected_skips"]
