"""Compressed-latent KV transport (ISSUE 20): shrink every byte moved.

The load-bearing properties: (1) MLA-native pools (DeepSeek-V2
``mla_cache_mode="compressed"``) export their shared latent directly —
bit-exact round-trips at a fraction of the decompressed bytes, with the
latent geometry folded into the block fingerprint so mismatched layouts
fail closed; (2) calibrated low-rank transport for GQA pools is opt-in
and bounded by the error stamped into the artifact at calibration time;
(3) every ``cache.compress`` fault degrades inside the existing counted
classification — encode faults ship the block RAW, decode faults land on the
consumer's re-prefill path, streams never drop and greedy streams stay
bit-identical on every exact path; (4) the spill tier re-accounts bytes
after the flusher compresses, turning compression into spill capacity.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.cache import KVCache
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.kv_compress import (
    CompressError,
    KVCompressCodec,
    KVCompressMap,
    ZeroLeaf,
    calibrate_compress_map,
    load_compress_map,
)
from mlx_sharding_tpu.kv_transfer import (
    BlockIntegrityError,
    KVPageBlock,
    KVSpillTier,
    export_block,
    import_block,
)
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.pod import LoopbackHub, PodFleet, PodPrefixFederation
from mlx_sharding_tpu.prefix_store import PrefixStore
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.testing import faults
from tests.helpers import hard_timeout, run_concurrent

TINY = dict(
    vocab_size=256,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
)

PAGE = 4


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    faults.disarm()


# --------------------------------------------------------------- helpers
def _dsv2_model(seed=3, layers=4, mla_cache_mode="compressed"):
    from mlx_sharding_tpu.config import DeepseekV2Config
    from mlx_sharding_tpu.models.deepseek_v2 import DeepseekV2Model

    cfg = DeepseekV2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=layers,
        num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
        q_lora_rank=None, qk_rope_head_dim=8, qk_nope_head_dim=16,
        v_head_dim=12, n_routed_experts=4, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1,
        mla_cache_mode=mla_cache_mode,
    )
    model = DeepseekV2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed), jnp.float32)
    return model, params


def _h1_pool_cache(pool_pages=6, page=PAGE, d_lat=24):
    """A hand-built MLA-shaped pool: ONE latent head of width ``d_lat``
    in k, the dummy all-zero ``(…, 1, 1)`` v buffer the compressed cache
    mode allocates (models/deepseek_v2.py)."""
    kshape = (1, 2, pool_pages + 1, 1, page, 1, d_lat)
    k = jnp.arange(np.prod(kshape), dtype=jnp.float32).reshape(kshape)
    v = jnp.zeros(kshape[:-2] + (1, 1), jnp.float32)
    return KVCache(k=k, v=v, offset=jnp.zeros((), jnp.int32))


def _latent_codec(d_lat=24):
    return KVCompressCodec(
        "latent", num_heads=1, head_dim_k=d_lat, head_dim_v=1
    )


def _export(cache, codec=None, pages=(2, 4)):
    return export_block(
        cache, list(pages), page_size=PAGE, n_tokens=6,
        prompt=[1, 2, 3], history=[5, 6, 7], produced=3,
        resume_keys=None, resume_recent=None, codec=codec,
    )


def _zero_like(cache):
    return KVCache(
        k=jax.tree.map(jnp.zeros_like, cache.k),
        v=jax.tree.map(jnp.zeros_like, cache.v),
        offset=jnp.zeros((), jnp.int32),
    )


def _lowrank_fixture(rank=4, L=2, H=2, D=4, pool_pages=6, seed=0):
    """Pool pages drawn from an exactly-rank-``rank`` row process plus
    the map calibrated on the same process: the SVD recovers the true
    basis, so reconstruction error is wire-float16 noise, well inside
    the stamped calibration bound."""
    rng = np.random.default_rng(seed)
    basis = np.linalg.qr(rng.normal(size=(H * D, H * D)))[0][:, :rank]

    def draw(shape_rows):
        coef = rng.normal(size=shape_rows + (rank,)).astype(np.float32)
        return (coef @ basis.T).astype(np.float32)

    cal_k = draw((L, 1, 64)).reshape(L, 1, 64, H, D)
    cal_v = draw((L, 1, 64)).reshape(L, 1, 64, H, D)
    m = calibrate_compress_map(cal_k, cal_v, rank=rank)
    kshape = (1, L, pool_pages + 1, 1, PAGE, H, D)
    k = draw((1, L, pool_pages + 1, 1, PAGE)).reshape(kshape)
    v = draw((1, L, pool_pages + 1, 1, PAGE)).reshape(kshape)
    cache = KVCache(
        k=jnp.asarray(k), v=jnp.asarray(v), offset=jnp.zeros((), jnp.int32)
    )
    codec = KVCompressCodec(
        "lowrank", compress_map=m, num_heads=H, head_dim_k=D, head_dim_v=D
    )
    return cache, m, codec


# -------------------------------------------------------------- artifact
def test_map_artifact_roundtrip_truncate_and_tamper(tmp_path):
    _, m, _ = _lowrank_fixture()
    path = str(tmp_path / "map.npz")
    m.save(path)
    loaded = KVCompressMap.load(path)
    assert loaded.compress_hash == m.compress_hash
    assert loaded.meta["calibration"]["max_rel_err"] < 1e-4

    # nested-SVD truncation: exact slice, distinct layout identity
    t2 = m.truncate(2)
    assert t2.rank == 2 and t2.compress_hash != m.compress_hash
    np.testing.assert_array_equal(t2.k_down, m.k_down[:, :, :2])
    assert load_compress_map(path, rank=2).compress_hash == t2.compress_hash
    with pytest.raises(CompressError, match="rank"):
        m.truncate(99)

    # rank without a map is a flag error, not a silent no-op
    with pytest.raises(CompressError, match="kv-compress-map"):
        load_compress_map(None, rank=2)
    assert load_compress_map(None) is None

    # an edited artifact is rejected against its own stamped hash
    import json

    import numpy as _np
    with _np.load(path) as z:
        doc = {n: _np.asarray(z[n]) for n in z.files}
    doc["k_down"] = doc["k_down"] * 1.5
    with open(path, "wb") as f:
        _np.savez(f, **doc)
    with pytest.raises(CompressError, match="recalibrate"):
        KVCompressMap.load(path)
    # and a foreign-format artifact fails with the expected-format hint
    bad = str(tmp_path / "bad.npz")
    with _np.load(path) as z:
        doc2 = {n: _np.asarray(z[n]) for n in z.files}
    hdr = json.loads(bytes(doc2["header"]).decode())
    hdr["format"] = "nope"
    doc2["header"] = _np.frombuffer(
        json.dumps(hdr).encode(), _np.uint8).copy()
    with open(bad, "wb") as f:
        _np.savez(f, **doc2)
    with pytest.raises(CompressError, match="mst-kv-compress-map-v1"):
        KVCompressMap.load(bad)


def test_map_geometry_and_share_validation_hints():
    _, m, _ = _lowrank_fixture()
    with pytest.raises(CompressError, match="recalibrate"):
        m.validate_for(3, m.num_heads, m.head_dim_k, m.head_dim_v)
    with pytest.raises(CompressError, match="kv-share-map"):
        m.validate_for(m.num_layers, m.num_heads, m.head_dim_k,
                       m.head_dim_v, share_hash="aa55")


# ----------------------------------------------------- MLA-native latent
def test_latent_export_roundtrip_bitexact_and_smaller():
    src = _h1_pool_cache()
    codec = _latent_codec()
    raw = _export(src).to_host()
    blk = _export(src, codec=codec).to_host()
    assert blk.compress_kind == "latent"
    assert blk.compress_hash == codec.compress_hash
    # the dummy-V leaves left the wire: strictly fewer bytes than raw
    assert blk.nbytes < raw.nbytes
    assert all(isinstance(leaf, ZeroLeaf)
               for leaf in jax.tree.leaves(
                   blk.v_pages,
                   is_leaf=lambda x: isinstance(x, ZeroLeaf)))

    # wire round-trip + demand reconstruction: bit-exact vs the raw path
    wire = KVPageBlock.from_bytes(blk.to_bytes())
    wire.verify()
    dst_a = import_block(_zero_like(src), wire, [1, 3], codec=codec)
    dst_b = import_block(_zero_like(src), raw, [1, 3])
    for a, b in zip(jax.tree.leaves((dst_a.k, dst_a.v)),
                    jax.tree.leaves((dst_b.k, dst_b.v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = codec.stats()
    assert s["mode"] == "latent" and s["blocks_compressed"] == 1
    assert s["blocks_reconstructed"] == 1
    assert s["bytes_saved_total"] > 0


def test_latent_wire_tamper_rejected():
    blk = _export(_h1_pool_cache(), codec=_latent_codec()).to_host()
    data = bytearray(blk.to_bytes())
    data[len(data) // 2] ^= 0xFF
    with pytest.raises(BlockIntegrityError):
        KVPageBlock.from_bytes(bytes(data)).verify()


def test_compress_layout_mismatch_fails_closed():
    src = _h1_pool_cache()
    blk = _export(src, codec=_latent_codec()).to_host()
    # a pool with no codec cannot reconstruct the latent payload
    with pytest.raises(BlockIntegrityError, match="compress layout"):
        import_block(_zero_like(src), blk, [1, 3])
    # nor can a codec of a different latent geometry
    with pytest.raises(BlockIntegrityError, match="compress layout"):
        import_block(_zero_like(src), blk, [1, 3],
                     codec=_latent_codec(d_lat=25))


def test_latent_prefetch_stages_reconstructed_pages():
    """prefetch() on a compressed block stages the RECONSTRUCTED form, so
    the tick-side import touches only dense pages (MST116 discipline)."""
    src = _h1_pool_cache()
    codec = _latent_codec()
    blk = _export(src, codec=codec).to_host()
    blk.prefetch(codec=codec)
    assert blk.is_prefetched
    dst = import_block(_zero_like(src), blk, [1, 3], codec=codec)
    ref = import_block(_zero_like(src), _export(src).to_host(), [1, 3])
    for a, b in zip(jax.tree.leaves((dst.k, dst.v)),
                    jax.tree.leaves((ref.k, ref.v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- calibrated low-rank
def test_lowrank_roundtrip_within_calibrated_bound():
    src, m, codec = _lowrank_fixture()
    blk = _export(src, codec=codec).to_host()
    assert blk.compress_kind == "lowrank"
    assert np.asarray(blk.k_pages).dtype == np.float16
    assert blk.nbytes * 2 <= _export(src).to_host().nbytes

    dst = import_block(_zero_like(src), blk, [2, 4], codec=codec)
    ref = import_block(_zero_like(src), _export(src).to_host(), [2, 4])
    for a, b in zip(jax.tree.leaves((dst.k, dst.v)),
                    jax.tree.leaves((ref.k, ref.v))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        denom = max(float(np.linalg.norm(b)), 1e-12)
        # exactly-rank-r rows: the only loss left is float16 wire noise,
        # comfortably inside the artifact's documented tolerance + eps
        assert float(np.linalg.norm(a - b)) / denom < 5e-3


def test_lowrank_block_rejected_by_other_calibration():
    src, _, codec = _lowrank_fixture(seed=0)
    _, _, other = _lowrank_fixture(seed=7)
    blk = _export(src, codec=codec).to_host()
    assert codec.compress_hash != other.compress_hash
    with pytest.raises(BlockIntegrityError, match="compress layout"):
        import_block(_zero_like(src), blk, [2, 4], codec=other)


# ------------------------------------------------------ fault degradation
def test_encode_fault_ships_block_raw():
    src = _h1_pool_cache()
    codec = _latent_codec()
    faults.arm("cache.compress", exc=faults.FaultError, times=1)
    blk = _export(src, codec=codec).to_host()
    # the block still moved — just uncompressed — and the fault counted
    assert blk.compress_kind is None and blk.is_host
    assert codec.stats()["compress_faults"] == 1
    dst = import_block(_zero_like(src), blk, [1, 3], codec=codec)
    ref = import_block(_zero_like(src), _export(src).to_host(), [1, 3])
    for a, b in zip(jax.tree.leaves((dst.k, dst.v)),
                    jax.tree.leaves((ref.k, ref.v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_fault_is_counted_integrity_error():
    src = _h1_pool_cache()
    codec = _latent_codec()
    blk = _export(src, codec=codec).to_host()
    faults.arm("cache.compress", exc=faults.FaultError, times=1)
    with pytest.raises(BlockIntegrityError, match="reconstruction"):
        import_block(_zero_like(src), blk, [1, 3], codec=codec)
    assert codec.stats()["reconstruct_faults"] == 1
    # the fault was transient: the same block imports fine afterwards
    import_block(_zero_like(src), blk, [1, 3], codec=codec)


# ------------------------------------------------------------- spill tier
def test_spill_tier_reaccounts_compressed_bytes():
    src = _h1_pool_cache()
    codec = _latent_codec()
    tier = KVSpillTier(1 << 20, flush_async=False)
    blk = _export(src, codec=codec)
    raw_nbytes = _export(src).to_host().nbytes
    assert tier.put("a", blk)
    s = tier.stats()
    # the flush compressed the payload; the budget charges WIRE bytes
    assert blk.compress_kind == "latent"
    assert s["bytes_in_use"] == blk.nbytes < raw_nbytes
    assert s["bytes_compress_saved"] == raw_nbytes - blk.nbytes
    got = tier.take("a")
    dst = import_block(_zero_like(src), got, [1, 3], codec=codec)
    ref = import_block(_zero_like(src), _export(src).to_host(), [1, 3])
    for a, b in zip(jax.tree.leaves((dst.k, dst.v)),
                    jax.tree.leaves((ref.k, ref.v))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tier.stats()["bytes_in_use"] == 0
    tier.close()


# ----------------------------------------------------------- prefix store
def test_prefix_store_bind_compress_hash_write_once():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    store.bind_compress_hash("aa55")
    store.bind_compress_hash("aa55")  # idempotent re-bind
    with pytest.raises(ValueError, match="kv-compress-map"):
        store.bind_compress_hash("bb66")
    store.close()


def test_prefix_store_host_put_rejects_foreign_compress_layout():
    src = _h1_pool_cache()
    codec = _latent_codec()
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    store.bind_compress_hash(codec.compress_hash)
    digests = store.digests_for(list(range(4 * PAGE)))
    ours = _export(src, codec=codec).to_host()
    theirs = _export(src, codec=_latent_codec(d_lat=25)).to_host()
    raw = _export(src).to_host()
    before = store.stats()["demote_drops"]
    assert store.host_put(digests[0], ours) is True
    assert store.host_put(digests[1], raw) is True  # raw always binds
    assert store.host_put(digests[2], theirs) is False
    assert store.stats()["demote_drops"] == before + 1
    store.close()


# ------------------------------------------------------ pod federation
def _peer(keys, *, age_s=0.0, page_size=PAGE, share=None, compress=None):
    return {"info": {"prefix": {"keys": list(keys), "page_size": page_size,
                                "share": share, "compress": compress}},
            "age_s": age_s}


def _fed(store, peers):
    class _T:
        def __init__(self):
            self.sent = []
            self.respond = None

        def peers(self):
            return peers

        def send(self, host, kind, payload):
            self.sent.append((host, kind, payload))
            if self.respond is not None:
                self.respond(host, kind, payload)

    t = _T()
    return PodPrefixFederation(0, t, store, fetch_timeout_s=0.25), t


def test_federation_heartbeat_advertises_and_checks_compress_hash():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    store.bind_compress_hash("aa55")
    hexd = store.digests_for(list(range(2 * PAGE)))[-1].hex()
    fed, t = _fed(store, {
        1: _peer([hexd], compress="bb66"),   # foreign latent layout
        2: _peer([hexd], compress=None),     # raw peer: also a mismatch
    })
    assert fed.local_info()["compress"] == "aa55"
    # every advertising peer is layout-incompatible: counted skip BEFORE
    # any bytes move, and the digest is negative-cached like a miss
    assert fed._owner_for(hexd) == (None, "layout_mismatch")
    digest = store.digests_for(list(range(2 * PAGE)))[-1]
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"layout_mismatch": 1}
    assert t.sent == []
    assert fed.fetch(digest) is False  # neg-cached now
    assert fed.stats()["fallbacks"]["neg_cached"] == 1
    store.close()


def test_federation_fetch_rejects_mismatched_blob_counted():
    """The owner re-calibrated between gossip and fetch: the blob's
    compress_hash no longer matches — counted layout_mismatch, plain
    prefill, never an import of an unreconstructable payload."""
    src = _h1_pool_cache()
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    store.bind_compress_hash(_latent_codec().compress_hash)
    digest = store.digests_for(list(range(2 * PAGE)))[-1]
    hexd = digest.hex()
    fed, t = _fed(store, {
        1: _peer([hexd], compress=_latent_codec().compress_hash),
    })
    blob = _export(src, codec=_latent_codec(d_lat=25)).to_host().to_bytes()

    def respond(host, kind, payload):
        rid = pickle.loads(payload)["rid"]
        fed.handle(1, "prefix.blob",
                   pickle.dumps((rid, blob),
                                protocol=pickle.HIGHEST_PROTOCOL))

    t.respond = respond
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"layout_mismatch": 1}
    assert fed.stats()["fetches"] == 0
    store.close()


# ---------------------------------------------------------- engine wiring
@pytest.fixture(scope="module")
def tiny_llama():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return model, params


def _llama_engine(tiny_llama, dev_idx=0, compress_map=None, kv_dtype=None,
                  pool_pages=10):
    model, params = tiny_llama
    devices = jax.devices()
    return PipelineEngine(
        model, params, make_mesh(pp=1, devices=devices[dev_idx:dev_idx + 1]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, pool_pages=pool_pages, page_size=8,
        kv_dtype=kv_dtype, kv_compress_map=compress_map,
    )


def _llama_map(rank=4):
    # llama TINY pool geometry: 2 layers, 2 kv heads, head_dim 8
    rng = np.random.default_rng(1)
    k = rng.normal(size=(2, 1, 32, 2, 8)).astype(np.float32)
    v = rng.normal(size=(2, 1, 32, 2, 8)).astype(np.float32)
    return calibrate_compress_map(k, v, rank=rank)


def test_engine_builds_codec_mla_native():
    def engine(model, params, **kw):
        return PipelineEngine(
            model, params, make_mesh(pp=1, devices=jax.devices()[:1]),
            microbatches=2, max_seq=64, cache_dtype=jnp.float32,
            prefill_chunk=8, pool_pages=10, page_size=8, **kw,
        )

    model, params = _dsv2_model()
    eng = engine(model, params)
    assert eng.kv_codec is not None and eng.kv_codec.mode == "latent"
    assert eng.kv_compress_hash == eng.kv_codec.compress_hash
    assert eng.kv_compress_stats()["mode"] == "latent"
    # what the latent saves on the wire, counted from shapes: the same
    # pages of a full-mode pool ship every head's keys and values
    full = engine(*_dsv2_model(mla_cache_mode="full"))
    assert full.kv_codec is None
    moved = {
        e: export_block(
            e.init_cache_paged()[0], [1, 2, 3, 4], page_size=8, n_tokens=32,
            prompt=[1, 2, 3], history=[1] * 29, produced=29,
            resume_keys=None, resume_recent=None, codec=e.kv_codec,
        ).to_host().nbytes
        for e in (eng, full)
    }
    c = model.config
    assert moved[full] * (c.kv_lora_rank + c.qk_rope_head_dim) == moved[eng] * (
        c.num_attention_heads
        * (c.qk_nope_head_dim + c.qk_rope_head_dim + c.v_head_dim)
    )
    # a map on an MLA-native pool is redundant, not silently layered
    with pytest.raises(CompressError, match="redundant"):
        engine(model, params, kv_compress_map=_llama_map())


def test_engine_codec_gates(tiny_llama):
    # no map, no MLA: no codec, zero behavior change
    assert _llama_engine(tiny_llama).kv_codec is None
    # a fitting map builds a lowrank codec
    eng = _llama_engine(tiny_llama, compress_map=_llama_map())
    assert eng.kv_codec.mode == "lowrank"
    assert eng.kv_compress_stats()["rank"] == 4
    # int8 pools don't compose
    with pytest.raises(CompressError, match="int8"):
        _llama_engine(tiny_llama, compress_map=_llama_map(),
                      kv_dtype="int8")
    # mis-calibrated geometry fails closed with the remediation hint
    rng = np.random.default_rng(2)
    bad = calibrate_compress_map(
        rng.normal(size=(3, 1, 16, 2, 8)).astype(np.float32),
        rng.normal(size=(3, 1, 16, 2, 8)).astype(np.float32), rank=4)
    with pytest.raises(CompressError, match="recalibrate"):
        _llama_engine(tiny_llama, compress_map=bad)


# ------------------------------------------- end-to-end stream parity
def _mla_spill_batcher(pool_pages=8, **kw):
    """Same shape as test_kv_transfer's spill harness but on the
    MLA-native DSv2 pool: each request needs 6 of 8 pages, so
    over-commit preempts — and every spilled block flushes through the
    latent codec."""
    model, params = _dsv2_model()
    eng = PipelineEngine(
        model, params, make_mesh(pp=1, devices=jax.devices()[:1]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, pool_pages=pool_pages, page_size=8,
    )
    ref = Generator(model, params, max_seq=64, cache_dtype=jnp.float32,
                    prefill_chunk=8)
    batcher = ContinuousBatcher(
        eng, decode_block=3, overcommit=True, spill_bytes=64 << 20, **kw
    )
    return batcher, ref


MLA_JOBS = [
    ([7, 7, 2, 1], dict(max_tokens=40)),
    ([9, 4, 4, 6], dict(temperature=0.9, top_p=0.85, seed=321,
                        max_tokens=36)),
]


def _refs(ref, jobs):
    return [[t for t, _ in ref.generate_step(p, **kw)] for p, kw in jobs]


@pytest.mark.slow
@hard_timeout(300)
def test_mla_spill_preempt_resume_bitexact():
    """The tentpole acceptance (full-sweep cell, slow for the tier-1
    budget): preempted-then-resumed streams on the MLA-native pool ride
    compressed-latent spill blocks and stay bit-identical to
    never-preempted solo runs — and the codec actually moved fewer
    bytes than raw."""
    batcher, ref = _mla_spill_batcher()
    try:
        refs = _refs(ref, MLA_JOBS)
        got = run_concurrent(batcher, MLA_JOBS)
        assert got == refs
        s = batcher.spill_stats()
        assert s["preemptions"] > 0 and s["spill_hits"] > 0
        assert s["spill_fallbacks"] == 0
        cs = batcher.engine.kv_compress_stats()
        assert cs["blocks_compressed"] > 0
        assert cs["blocks_reconstructed"] > 0
        # the pool already holds the latent; the codec's own saving here
        # is just the dummy-v leaf. What a latent pool saves against a
        # full-mode one: test_engine_builds_codec_mla_native.
        assert cs["bytes_wire_total"] < cs["bytes_raw_total"]
        assert cs["compress_faults"] == 0 and cs["reconstruct_faults"] == 0
    finally:
        batcher.close()


@pytest.mark.slow
@hard_timeout(300)
def test_mla_spill_with_compress_faults_still_exact():
    """Full-sweep cell (slow for the tier-1 budget; the quick-tier
    encode/decode fault units + the compress_fault_handoff chaos
    scenario keep the contract gated): cache.compress armed across the
    run (encode AND decode legs hit arbitrarily): blocks ship raw /
    resumes re-prefill, counted, and every stream still matches the
    solo reference — zero drops."""
    batcher, ref = _mla_spill_batcher()
    try:
        refs = _refs(ref, MLA_JOBS)
        faults.arm("cache.compress", exc=faults.FaultError, times=2)
        got = run_concurrent(batcher, MLA_JOBS)
        faults.disarm()
        assert got == refs
        cs = batcher.engine.kv_compress_stats()
        assert cs["compress_faults"] + cs["reconstruct_faults"] >= 1
        # a second, unfaulted pass on the same pool also stays exact
        got2 = run_concurrent(batcher, MLA_JOBS)
        assert got2 == refs
    finally:
        batcher.close()


@pytest.mark.slow
@hard_timeout(300)
def test_lowrank_engine_greedy_close_and_stats(tiny_llama):
    """Full-sweep cell: the lossy low-rank path through a real batcher's
    spill/preempt flow — streams complete (no drops), the codec moved
    fewer bytes, and faults stayed zero. Token-exactness is NOT promised
    here (the path is lossy by contract; the artifact's stamped rel-err
    is the tolerance)."""
    model, params = tiny_llama
    eng = PipelineEngine(
        model, params, make_mesh(pp=1, devices=jax.devices()[:1]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, pool_pages=8, page_size=8,
        kv_compress_map=_llama_map(rank=12),
    )
    batcher = ContinuousBatcher(eng, decode_block=3, overcommit=True,
                                spill_bytes=64 << 20)
    try:
        got = run_concurrent(batcher, MLA_JOBS)
        assert all(len(toks) > 0 for toks in got)
        s = batcher.spill_stats()
        assert s["preemptions"] > 0
        cs = eng.kv_compress_stats()
        assert cs["blocks_compressed"] > 0
        assert cs["bytes_wire_total"] < cs["bytes_raw_total"]
        assert cs["compress_faults"] == 0 and cs["reconstruct_faults"] == 0
    finally:
        batcher.close()


@pytest.mark.slow
@hard_timeout(300)
def test_mla_federation_end_to_end_compressed_blob_bitexact():
    """Full-sweep cell: pod prefix federation on MLA-native engines —
    the blob that rides the fabric is the compressed latent, the compress
    hash matches through the heartbeat check, and the continued stream
    is bit-identical to a monolithic batcher."""
    model, params = _dsv2_model()

    def mk_host(dev_idx, with_store=True):
        eng = PipelineEngine(
            model, params,
            make_mesh(pp=1, devices=jax.devices()[dev_idx:dev_idx + 1]),
            microbatches=2, max_seq=64, cache_dtype=jnp.float32,
            prefill_chunk=8, pool_pages=10, page_size=8,
        )
        store = PrefixStore(host_bytes=1 << 20) if with_store else None
        return ContinuousBatcher(eng, decode_block=3,
                                 prefix_store=store), store

    base = [7, 7, 2, 1, 9, 4, 4, 6, 3, 17, 42, 5, 11, 2, 2, 8]
    b_a, store_a = mk_host(0)
    b_b, store_b = mk_host(1 % len(jax.devices()))
    mono, _ = mk_host(2 % len(jax.devices()), with_store=False)
    hub = LoopbackHub()
    f_a = PodFleet(0, hub.register(0), b_a, prefix_store=store_a)
    f_b = PodFleet(1, hub.register(1), b_b, prefix_store=store_b)
    try:
        assert store_a.compress_hash is not None
        assert store_a.compress_hash == store_b.compress_hash
        list(b_a.generate_step(base + [5], max_tokens=12))
        assert store_a.stats()["demotions"] >= 1
        f_a.tick()
        f_b.tick()
        assert f_a.prefix.local_info()["compress"] == store_a.compress_hash
        got = [t for t, _ in b_b.generate_step(base + [9], max_tokens=12)]
        ref = [t for t, _ in mono.generate_step(base + [9], max_tokens=12)]
        assert got == ref
        sb = f_b.prefix.stats()
        assert sb["fetches"] == 1 and sb["fetch_bytes"] > 0
        assert sb["fallbacks"].get("layout_mismatch", 0) == 0
    finally:
        f_a.close(close_local=False)
        f_b.close(close_local=False)
        b_a.close()
        b_b.close()
        mono.close()
