"""CPU rehearsal of chip_smoke.py: exactly the function the chip runs, at a
tiny size — and the proof that the command line refuses anything but a TPU.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _clear():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()


def _tiny():
    from pathway_tpu.models.encoder import EncoderConfig

    # room for the 4096 whole-word entries of the synthetic vocab
    return EncoderConfig.tiny(vocab_size=8192)


def _tiny_windowed():
    import jax.numpy as jnp

    from pathway_tpu.models.decoder import DecoderConfig

    return DecoderConfig.tiny_windowed(compute_dtype=jnp.float32,
                                       max_len=512, sliding_window_size=100)


def _tiny_latent():
    import jax.numpy as jnp

    from pathway_tpu.models.decoder import DecoderConfig

    return DecoderConfig.tiny_latent(compute_dtype=jnp.float32, max_len=512)


def test_smoke_function_passes_on_cpu_at_tiny_size(tmp_path):
    out = tmp_path / "topk.json"
    summary = chip_smoke.run_smoke(
        expected_platform="cpu", config=_tiny, n_docs=48, max_words=40,
        max_len=48, scan_rows=2048, request_timeout_s=60,
        out_path=str(out), decoder_config=_tiny_windowed, decoder_row=512,
        latent_config=_tiny_latent, latent_row=512)
    # one forward of the windowed decoder on a row of 512 slots: a document
    # of 320 tokens, window 100; heads of 32 features take the blockwise loop
    windowed = summary["windowed_decoder"]
    assert windowed["alone_vs_packed_cos"] > 0.9999
    assert windowed["lowerings"]["kernel"] == 0 \
        and windowed["lowerings"]["blockwise"] >= 4
    assert windowed["query_block"] == 256
    # and one of the latent decoder: two layers of two latent attention
    # sublayers; values of 16 features take the blockwise loop
    latent = summary["latent_decoder"]
    assert latent["alone_vs_packed_cos"] > 0.9999
    assert latent["lowerings"]["kernel"] == 0 \
        and latent["lowerings"]["blockwise"] >= 1
    assert latent["query_block"] == 512 \
        and latent["lowerings"]["query_block_512"] >= 1
    assert summary["device"]["platform"] == "cpu"
    assert summary["n_docs"] == 48 + 3
    assert summary["bridge_legs_resolved"] > 0
    assert summary["bf16_vs_f32_min_cos"] >= chip_smoke.BF16_VS_F32_MIN_COS
    assert summary["mesh"] is None and out.exists()


def test_the_decoder_check_of_the_pattern_that_chooses_its_keys():
    """The third pattern of ``_decoder_check`` at a tiny size: a dense
    layer with an indexer, two expert layers that share its choice and one
    that chooses again; a document of 320 tokens over the 24 best keys a
    query, alone in its row and behind two others; values of 32 features
    take the sparse blockwise loop and no other, at the query block of
    ungrouped heads, which the check returns."""
    import jax.numpy as jnp

    from pathway_tpu.models.decoder import DecoderConfig

    indexed = chip_smoke._decoder_check(
        DecoderConfig.tiny_indexed(compute_dtype=jnp.float32, max_len=512),
        512, 5, "indexed")
    assert indexed["alone_vs_packed_cos"] > 0.9999
    assert indexed["lowerings"]["sparse_blockwise"] >= 1
    assert not any(n for name, n in indexed["lowerings"].items()
                   if name not in ("sparse_blockwise", "query_block_512"))
    assert indexed["query_block"] == 512
    assert indexed["lowerings"]["query_block_512"] \
        == indexed["lowerings"]["sparse_blockwise"]


def test_smoke_refuses_the_wrong_platform_before_building_anything():
    built = []

    def config():
        built.append(1)
        return _tiny()

    with pytest.raises(chip_smoke.SmokeFailure, match="'cpu'.*needs 'tpu'"):
        chip_smoke.run_smoke(
            expected_platform="tpu", config=config, n_docs=8, max_words=8,
            max_len=16, scan_rows=64)
    assert not built


def test_command_line_refuses_a_cpu_whatever_the_environment():
    """`python chip_smoke.py` takes no flag and reads no variable that
    lets it pass off the chip: non-zero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", CHIP_SMOKE_PLATFORM="cpu",
               EXPECTED_PLATFORM="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--platform", "cpu", "--allow-cpu"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 'tpu'" in proc.stderr


def test_smoke_function_mesh_auto_on_virtual_devices():
    """The four-chip variant's rehearsal: mesh="auto" over the suite's 8
    virtual CPU devices must pick the sharded paged index and still pass
    every check (the fused-path checks do not apply on a mesh)."""
    from pathway_tpu.parallel import mesh as mesh_mod

    try:
        summary = chip_smoke.run_smoke(
            expected_platform="cpu", config=_tiny, n_docs=48, max_words=40,
            max_len=48, scan_rows=2048, mesh="auto", request_timeout_s=60)
    finally:
        mesh_mod._ACTIVE_MESH = None  # get_mesh() memoizes process-wide
    assert summary["mesh"] == "auto"
    assert summary["device"]["count"] == 8
