"""The DDP mixes hold the bucket list the tool computes from the model."""

import json

from benchlib.spec import BENCH


def test_mixes_hold_the_recomputed_buckets():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ddp_buckets", BENCH / "tools" / "ddp_buckets.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tensors = tool.registered_tensors()
    sizes = tool.buckets(tensors)
    params = sum(e for _, e in tensors)
    assert params == 2_422_386_848
    assert sum(sizes) == 4 * params
    assert sizes[0] >= tool.FIRST_BUCKET_BYTES
    for name in tool.MIXES:
        mix = json.loads((BENCH / "mixes" / f"{name}.json").read_text())
        assert mix["walk"]["payload_bytes"] == sizes
        assert mix["parameters"] == params
