"""One decode step, one choice, never a flag: the four flags that once
picked among the layer loops and the attention backends are gone for
good, and ``tools/served_programs.py`` shows what a refactoring of the
step serves (``tests/test_stream_grouped.py`` and
``tests/test_paged_backends.py`` hold the choices themselves)."""
import runpy

import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags


@pytest.mark.parametrize("name", [
    "paged_attention_backend", "decode_grouped", "decode_prefetch",
    "decode_linear"])
def test_removed_flag_is_unknown(name, monkeypatch):
    """A removed flag is any unknown flag: ``set_flags`` / ``get_flags``
    refuse it under both spellings, and its environment names are read
    by nobody — a registry built afresh under them does not hold it."""
    for spelled in (name, "FLAGS_" + name):
        with pytest.raises(ValueError, match="unknown flag"):
            paddle.set_flags({spelled: "on"})
        with pytest.raises(ValueError, match="unknown flag"):
            paddle.get_flags([spelled])
    monkeypatch.setenv("FLAGS_" + name, "on")
    monkeypatch.setenv(flags.env_var_for(name), "on")
    fresh = runpy.run_path(flags.__file__)["_FLAGS"]
    assert name not in fresh and name not in flags._FLAGS
    # core/flags.py: 53 flags before the four went, none new
    assert len(fresh) == 49 and set(fresh) <= set(flags._FLAGS)


def test_served_programs_tool_lowers_the_uniform_cell(tmp_path):
    """``tools/served_programs.py`` (the "same program" proof of a
    refactoring): gpt3-1.3b's two programs lower for the TPU off the
    chip, hold their kernels, carry no source location, and lower to the
    same text twice."""
    import tools.served_programs as sp

    first = sp.write_programs(str(tmp_path / "a"), ["gpt3-1.3b"])
    again = sp.write_programs(str(tmp_path / "b"), ["gpt3-1.3b"])
    by_name = {p.rsplit("/", 1)[1]: t for p, t in first.items()}
    assert sorted(by_name) == ["gpt3-1.3b.decode_chunk.mlir",
                               "gpt3-1.3b.prefill_chunk.mlir"]
    # QKV stream + in-place attention + fused tail; K/V write + attend
    assert by_name["gpt3-1.3b.decode_chunk.mlir"] \
        .count("tpu_custom_call") == 3
    assert by_name["gpt3-1.3b.prefill_chunk.mlir"] \
        .count("tpu_custom_call") == 2
    for name, text in by_name.items():
        assert text == again[str(tmp_path / "b" / name)]
        assert "loc(" not in text
        assert "39936x16x16x128xbf16" in text        # the cell's pool
