"""Assignment-table conformance for the 10 configs + launch-layer units."""
import pytest

from repro.configs import SHAPES, get_config, list_archs

from repro.launch.mesh import make_local_mesh
from repro.launch.roofline import model_flops, roofline

# (family, L, d_model, H, KV, d_ff, vocab) — verbatim from the assignment
ASSIGNED = {
    "recurrentgemma-2b": ("hybrid", 26, 2560, 10, 1, 7680, 256000),
    "llama-3.2-vision-11b": ("vlm", 40, 4096, 32, 8, 14336, 128256),
    "rwkv6-7b": ("ssm", 32, 4096, 64, 64, 14336, 65536),
    "moonshot-v1-16b-a3b": ("moe", 48, 2048, 16, 16, 1408, 163840),
    "granite-moe-1b-a400m": ("moe", 24, 1024, 16, 8, 512, 49155),
    "gemma-7b": ("dense", 28, 3072, 16, 16, 24576, 256000),
    "h2o-danube-1.8b": ("dense", 24, 2560, 32, 8, 6912, 32000),
    "minitron-8b": ("dense", 32, 4096, 32, 8, 16384, 256000),
    "granite-3-8b": ("dense", 40, 4096, 32, 8, 12800, 49155),
    "hubert-xlarge": ("audio", 48, 1280, 16, 16, 5120, 504),
}

MOE = {"moonshot-v1-16b-a3b": (64, 6), "granite-moe-1b-a400m": (32, 8)}


def test_all_ten_archs_registered():
    assert sorted(list_archs()) == sorted(ASSIGNED)


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_config_matches_assignment(arch):
    fam, L, d, H, KV, F, V = ASSIGNED[arch]
    cfg = get_config(arch)
    assert (cfg.family, cfg.n_layers, cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, cfg.d_ff, cfg.vocab) == (fam, L, d, H, KV, F, V)
    if arch in MOE:
        assert (cfg.n_experts, cfg.top_k) == MOE[arch]
    # special structure
    if arch == "recurrentgemma-2b":
        assert cfg.hybrid_period == 3 and cfg.window == 2048
        kinds = cfg.layer_kinds()
        assert kinds.count("attn") == 8 and kinds.count("rglru") == 18
    if arch == "llama-3.2-vision-11b":
        assert cfg.layer_kinds().count("xattn") == 8
    if arch == "rwkv6-7b":
        assert cfg.attention_free and cfg.resolved_head_dim == 64
    if arch == "hubert-xlarge":
        assert cfg.encoder_only
    if arch == "h2o-danube-1.8b":
        assert cfg.window == 4096
    if arch == "gemma-7b":
        assert cfg.resolved_head_dim == 256


def test_shape_cells_match_assignment():
    assert (SHAPES["train_4k"].seq_len, SHAPES["train_4k"].global_batch) \
        == (4096, 256)
    assert (SHAPES["prefill_32k"].seq_len,
            SHAPES["prefill_32k"].global_batch) == (32768, 32)
    assert (SHAPES["decode_32k"].seq_len,
            SHAPES["decode_32k"].global_batch) == (32768, 128)
    assert (SHAPES["long_500k"].seq_len,
            SHAPES["long_500k"].global_batch) == (524288, 1)


def test_padded_vocab_shards_over_tp():
    for arch in list_archs():
        assert get_config(arch).padded_vocab % 256 == 0


def test_roofline_terms_math():
    t = roofline(flops_per_chip=197e12, bytes_per_chip=819e9,
                 coll_bytes_per_chip=0.0, n_chips=256,
                 model_flops_total=197e12 * 256, device_kind="TPU v5 lite")
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.dominant in ("compute", "memory")
    assert t.useful_flops_ratio == pytest.approx(1.0)


def test_roofline_unknown_device_kind_raises():
    # peaks are per device kind: an unlisted chip (or the CPU) has none
    with pytest.raises(KeyError, match="no peak figures"):
        roofline(1.0, 1.0, 0.0, 1, 1.0, device_kind="cpu")


def test_model_flops_modes():
    cfg = get_config("granite-3-8b")
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    dc = model_flops(cfg, SHAPES["decode_32k"])
    assert tr == pytest.approx(6 * cfg.total_params() * 4096 * 256)
    assert pf == pytest.approx(2 * cfg.total_params() * 32768 * 32)
    assert dc == pytest.approx(2 * cfg.total_params() * 128)
    moe = get_config("moonshot-v1-16b-a3b")
    assert model_flops(moe, SHAPES["train_4k"]) < \
        6 * moe.total_params() * 4096 * 256   # active < total


def test_local_mesh_and_context():
    from repro.models import set_mesh_context, pspec
    mesh = make_local_mesh(1, 1)
    set_mesh_context(mesh)
    try:
        spec = pspec("batch", None, "model")
        assert spec[0] in (("data",), "data")    # P may canonicalise 1-tuples
        assert spec[2] == "model"
    finally:
        set_mesh_context(None)


def test_production_mesh_requires_512(monkeypatch):
    """make_production_mesh needs 512 host devices — on this 1-device test
    process it must raise rather than silently mis-shape."""
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(Exception):
        make_production_mesh(multi_pod=True)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_env_or_fixed_checkout_dir(monkeypatch,
                                                        tmp_path, env_dir):
    """The persistent compile cache sits where $JAX_COMPILATION_CACHE_DIR
    says, else at one fixed directory of the checkout — never a path that
    changes between runs."""
    import jax
    from repro.runtime import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        again = compile_cache.enable_compile_cache()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert got == again
    if env_dir:
        assert got == str(tmp_path / env_dir)
    else:
        assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert compile_cache.CHECKOUT_CACHE_DIR.parent == \
            compile_cache.pathlib.Path(__file__).resolve().parents[1]
