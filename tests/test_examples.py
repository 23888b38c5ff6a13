"""Smoke tests: the runnable example apps (examples/ — the equivalents of
the reference's spark-cobol-app programs) must stay green. The device
query example is TPU-targeted (minutes of XLA compile on CPU); the
aggregate it shows is held by tests/test_device_query.py at a small
size and by chip_smoke.py's aggregate phase on the chip."""
import importlib.util
import os
import sys

import pytest

_EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")


def _run_example(name: str):
    spec = importlib.util.spec_from_file_location(
        f"example_{name}", os.path.join(_EXAMPLES, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    try:
        spec.loader.exec_module(mod)
        mod.main()
    finally:
        sys.modules.pop(spec.name, None)


@pytest.mark.parametrize("name", ["types_app", "multisegment_app",
                                  "codec_app", "hierarchical_app",
                                  "remote_storage_app",
                                  "lakehouse_sink_app"])
def test_example_runs(name, capsys):
    _run_example(name)
    out = capsys.readouterr().out
    assert out.strip()
