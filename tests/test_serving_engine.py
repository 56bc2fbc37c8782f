"""Continuous-batching serve engine guarantees
(docs/continuous-batching.md).

Three groups:

1. **Allocator invariants** — hypothesis traffic over
   ``PagedKvAllocator``: no page is ever owned by two live requests, a
   release (retire or preemption) returns every owned page,
   ``used + free == num_pages`` at every point, and ownership is exactly
   ``ceil(covered_rows / page_size)`` pages.
2. **Token identity** — the engine's per-request greedy tokens equal a
   batch-1 static decode (``generate()`` semantics) at the same global
   ``max_len``, for all six cache families (GQA / MLA / SSM / hybrid /
   enc-dec / VLM) and the int8 KV fallback: batching policy must never
   move numerics.
3. **Paged memory bitwise** — ``engine.memory_bytes()`` equals
   ``concrete_paged_cache_bytes`` at dp == tp == 1, the symbolic serve
   estimate equals ``LoweredPlan.memory_report()`` on paged serve
   shapes, and the engine's probe-based leaf classification agrees with
   the layout derivation's.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property tests skip; example tests still run
    HAVE_HYPOTHESIS = False

from repro.configs.base import ShapeConfig, get_arch
from repro.core.plan import Plan, single_stage_plan
from repro.launch.mesh import make_host_mesh
from repro.lowering import lower_plan
from repro.lowering.cache_layout import (concrete_paged_cache_bytes,
                                         derive_cache_layout, is_paged_leaf)
from repro.models.zoo import build_model, pad_caches, quantize_caches
from repro.serving import (ContinuousBatchingEngine, ContinuousScheduler,
                           PagedKvAllocator, ServeRequest, pages_for)
from repro.serving.pages import classify_cache_tree
from repro.training.step import make_prefill_step, make_serve_step

# one arch per KV/state cache family (mirrors tests/test_serve_correctness)
FAMILY_ARCHS = {
    "gqa": "granite-3-8b",
    "mla": "minicpm3-4b",
    "ssm": "xlstm-1.3b",
    "hybrid": "zamba2-2.7b",
    "encdec": "whisper-small",
    "vlm": "internvl2-1b",
}

SLOTS, PAGE = 2, 8
PLENS, GENS = (6, 10), (6, 3)


# -- 1. allocator invariants ---------------------------------------------------


class TestAllocator:
    def test_lowest_id_first_and_release_returns_all(self):
        a = PagedKvAllocator(num_pages=6, page_size=4)
        assert a.admit("r0", rows=9) == [0, 1, 2]     # ceil(9/4)
        assert a.admit("r1", rows=1) == [3]
        assert a.used == 4 and a.free == 2
        assert a.extend("r0", rows=13) == [4]
        assert a.extend("r0", rows=13) == []          # already covered
        assert a.extend("r1", rows=24) is None        # 6 - 2 free < 5
        assert sorted(a.release("r0")) == [0, 1, 2, 4]
        assert a.used == 1 and a.free == 5
        assert a.highwater == 5

    def test_watermark_gates_admission_only(self):
        a = PagedKvAllocator(num_pages=4, page_size=2, watermark=2)
        assert a.can_admit(4)                          # leaves 2 free
        assert not a.can_admit(6)                      # would leave 1
        assert a.can_admit(6, ignore_watermark=True)
        a.admit("r0", rows=4)
        # extension may dip below the watermark freely
        assert a.extend("r0", rows=8) == [2, 3]
        with pytest.raises(RuntimeError):
            a.admit("r1", rows=1)

    def test_double_admit_rejected(self):
        a = PagedKvAllocator(num_pages=2, page_size=2)
        a.admit("r0", rows=1)
        with pytest.raises(ValueError):
            a.admit("r0", rows=1)

    if HAVE_HYPOTHESIS:
        @given(st.data())
        @settings(max_examples=60, deadline=None)
        def test_invariants_under_random_traffic(self, data):
            num_pages = data.draw(st.integers(1, 24), label="num_pages")
            page_size = data.draw(st.integers(1, 8), label="page_size")
            a = PagedKvAllocator(num_pages=num_pages, page_size=page_size)
            covered = {}                    # rid -> rows granted so far
            next_rid = 0
            max_rows = num_pages * page_size
            for _ in range(data.draw(st.integers(1, 40), label="steps")):
                ops = ["admit"] + (["extend", "release"] if covered else [])
                op = data.draw(st.sampled_from(ops), label="op")
                if op == "admit":
                    rows = data.draw(st.integers(1, max_rows), label="rows")
                    if a.can_admit(rows):
                        pages = a.admit(next_rid, rows)
                        assert len(pages) == pages_for(rows, page_size)
                        covered[next_rid] = rows
                        next_rid += 1
                    else:
                        with pytest.raises(RuntimeError):
                            a.admit(next_rid, rows)
                elif op == "extend":
                    rid = data.draw(st.sampled_from(sorted(covered)),
                                    label="rid")
                    rows = data.draw(st.integers(1, max_rows), label="rows")
                    need = (pages_for(rows, page_size)
                            - len(a.pages(rid)))
                    free_before = a.free
                    got = a.extend(rid, rows)
                    if got is None:                    # pool exhausted
                        assert need > free_before
                    else:
                        assert len(got) == max(0, need)
                        covered[rid] = max(covered[rid], rows)
                else:
                    rid = data.draw(st.sampled_from(sorted(covered)),
                                    label="rid")
                    freed = a.release(rid)
                    assert len(freed) == pages_for(covered.pop(rid),
                                                   page_size)
                # global invariants, every step
                assert a.used + a.free == num_pages
                owned = [p for rid in a.owners() for p in a.pages(rid)]
                assert len(owned) == len(set(owned))       # no aliasing
                assert len(owned) == a.used
                for rid in a.owners():
                    assert len(a.pages(rid)) == pages_for(covered[rid],
                                                          page_size)
            for rid in list(a.owners()):
                a.release(rid)
            assert a.free == num_pages                 # everything freed


# -- scheduler policy ----------------------------------------------------------


class TestScheduler:
    def test_preempt_youngest_requeues_at_head(self):
        alloc = PagedKvAllocator(num_pages=4, page_size=4)
        sched = ContinuousScheduler(slots=2, allocator=alloc)
        a, b = ServeRequest("a", {}, 8), ServeRequest("b", {}, 8)
        sched.submit(a)
        sched.submit(b)
        sa = sched.admit(a, rows=7)                    # 2 pages
        sb = sched.admit(b, rows=7)                    # 2 pages: pool full
        b.prefilled = ("tok", "caches", 7)
        sched.active[sa].pos = 8      # next step writes row 8: third page
        assert sched.ensure_coverage(sa) is None       # exhausted
        victim = sched.preempt_youngest()
        assert victim == sb
        assert sched.waiting[0] is b                   # requeued at HEAD
        assert b.prefilled is None                     # full replay
        assert alloc.free == 2
        assert sched.ensure_coverage(sa) == [2]        # now succeeds

    def test_retire_frees_slot_and_pages(self):
        alloc = PagedKvAllocator(num_pages=4, page_size=4)
        sched = ContinuousScheduler(slots=1, allocator=alloc)
        r = ServeRequest("r", {}, 2)
        sched.submit(r)
        slot = sched.admit(r, rows=3)
        assert not sched.can_try_admit()               # no free slot
        sched.retire(slot)
        assert alloc.free == 4 and not sched.active

    def test_peak_pages_covers_admission_and_tail(self):
        alloc = PagedKvAllocator(num_pages=8, page_size=4)
        sched = ContinuousScheduler(slots=1, allocator=alloc)
        assert sched.peak_pages(rows=3, max_new=1) == 1    # admit: rows+1
        assert sched.peak_pages(rows=3, max_new=14) == 4   # tail: rows+13


# -- 2 + 3. per-family token identity and the bitwise memory contract ----------


def _prompt_batch(fam, cfg, plen, seed):
    k = jax.random.PRNGKey(seed)
    b = {"tokens": jax.random.randint(k, (1, plen), 0,
                                      cfg.vocab_size).astype(jnp.int32)}
    if fam == "vlm":
        b["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 1),
            (1, cfg.num_patches, cfg.d_model)).astype(jnp.bfloat16)
    if fam == "encdec":
        b["frames"] = jax.random.normal(
            jax.random.fold_in(k, 1),
            (1, cfg.encoder_seq, cfg.d_model)).astype(jnp.bfloat16)
    return b


def _static_ref(model, params, low, prompt, gen, max_len, kv8):
    """generate() semantics at batch 1: real prefill, padded contiguous
    cache at the engine's global max_len, greedy decode."""
    prefill = make_prefill_step(model, return_cache=True, lowered=low)
    logits, caches = prefill.fn(params, prompt)
    if kv8:
        caches = quantize_caches(caches)
    rows = None
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key == "pos":
            rows = int(np.asarray(leaf).reshape(-1)[0])
            break
    if rows is None:                    # pure-state families (SSM)
        rows = prompt["tokens"].shape[1]
    caches = pad_caches(caches, max_len - rows)
    serve = make_serve_step(model, batch=1, max_len=max_len, donate=False,
                            lowered=low)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out = [int(tok[0, 0])]
    for _ in range(gen - 1):
        logits, caches = serve.fn(params, tok, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out.append(int(tok[0, 0]))
    return np.asarray(out, np.int32)


def _run_family(fam, arch, kv_dtype="bf16"):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    max_len = 64 if fam == "vlm" else 32
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0,
                             kv_cache_dtype=kv_dtype, page_size=PAGE)
    mesh = make_host_mesh(1, 1)
    low = lower_plan(cfg, None, plan, mesh)
    with jax.set_mesh(mesh):
        params, _ = model.init(jax.random.PRNGKey(0))
        kv8 = kv_dtype == "int8"
        eng = ContinuousBatchingEngine(model, params, plan, mesh,
                                       slots=SLOTS, max_len=max_len,
                                       page_size=PAGE, lowered=low)
        prompts = [_prompt_batch(fam, cfg, pl, 100 + i)
                   for i, pl in enumerate(PLENS)]
        for i, (p, g) in enumerate(zip(prompts, GENS)):
            eng.submit(p, g, rid=i)
        res = eng.run()
        for i, (p, g) in enumerate(zip(prompts, GENS)):
            ref = _static_ref(model, params, low, p, g, max_len, kv8)
            assert np.array_equal(res[i], ref), \
                f"{fam}: request {i} diverged: {res[i]} != {ref}"
        # the bitwise paged-memory contract, on the engine's REAL arrays
        want = int(concrete_paged_cache_bytes(cfg, SLOTS, max_len, PAGE,
                                              kv_dtype, dp_size=1,
                                              tp_size=1))
        assert eng.memory_bytes() == want


@pytest.mark.parametrize("fam", sorted(FAMILY_ARCHS))
def test_paged_decode_token_identical(fam):
    """Every cache family: continuous/paged decode emits exactly the
    static path's tokens, and the engine's allocation matches the
    derived paged layout byte for byte."""
    _run_family(fam, FAMILY_ARCHS[fam])


def test_paged_decode_token_identical_int8():
    """The int8 KV fallback pages quantized k/v + f32 scales; identity
    holds against the int8 static path (same quantize, same pages)."""
    _run_family("gqa", FAMILY_ARCHS["gqa"], kv_dtype="int8")


@pytest.mark.parametrize("fam", sorted(FAMILY_ARCHS))
def test_classification_agrees_with_layout(fam):
    """The engine's probe-based leaf classification and the layout
    derivation's ``is_paged_leaf`` are the SAME predicate — otherwise
    the memory contract could pass by coincidence."""
    cfg = get_arch(FAMILY_ARCHS[fam]).reduced()
    model = build_model(cfg)
    max_len = 64 if fam == "vlm" else 32
    specs = classify_cache_tree(model.init_caches, SLOTS, max_len,
                                jnp.bfloat16)
    layout = derive_cache_layout(cfg, SLOTS, max_len, "bf16")
    assert [s.paged for s in specs] \
        == [is_paged_leaf(lf, max_len) for lf in layout.leaves]
    assert [s.key for s in specs] == [lf.key for lf in layout.leaves]


def test_paged_estimate_matches_memory_report():
    """Two-evaluation contract on paged serve shapes: the symbolic serve
    model prices plan.page_size > 0 with pool bytes that equal the
    lowered ``memory_report()`` bitwise."""
    import dataclasses
    from repro.core.costmodel import estimate_serve_plan
    cfg = get_arch("granite-3-8b").reduced()
    base = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0)
    shape = ShapeConfig("serve", 32, 2, "decode")
    mesh = jax.sharding.AbstractMesh((1, 1), ("data", "model"))
    seen = set()
    for ps in (0, 8, 16):
        plan = dataclasses.replace(base, page_size=ps)
        rep = lower_plan(cfg, shape, plan, mesh).memory_report()
        est = estimate_serve_plan(cfg, shape, plan)
        assert est["mem_decode"] == rep.peak_bytes, (ps, est["mem_decode"],
                                                     rep.peak_bytes)
        seen.add(rep.peak_bytes)
    assert len(seen) == 3      # paging really moves the priced bytes


def test_page_size_plan_json_round_trip():
    """page_size survives Plan JSON; the 0 default is OMITTED so every
    pre-existing golden plan fixture stays byte-identical."""
    base = single_stage_plan(4, dp=1, tp=1, micro_batch=1, grad_accum=1)
    assert '"page_size"' not in base.to_json()
    paged = single_stage_plan(4, dp=1, tp=1, micro_batch=1, grad_accum=1,
                              page_size=16)
    doc = paged.to_json()
    assert '"page_size": 16' in doc
    assert Plan.from_json(doc).page_size == 16
    assert Plan.from_json(base.to_json()).page_size == 0


def test_tuner_page_grid_sweeps_and_defaults():
    """The serve tuner: no page_grid and page_grid=(0,) are byte-
    identical (golden stability); a real grid yields a plan whose
    page_size is drawn from it and priced consistently."""
    from repro.core.tuner import MistTuner, TuneSpec
    cfg = get_arch("granite-3-8b").reduced()
    kw = dict(arch=cfg, seq_len=64, global_batch=4, n_devices=1,
              space="serve")
    r_none = MistTuner(TuneSpec(**kw)).tune()
    r_zero = MistTuner(TuneSpec(**kw, page_grid=(0,))).tune()
    assert r_none.plan.to_json() == r_zero.plan.to_json()
    assert r_none.plan.page_size == 0
    r_grid = MistTuner(TuneSpec(**kw, page_grid=(0, 8, 16))).tune()
    assert r_grid.plan.page_size in (0, 8, 16)
    assert r_grid.n_swept >= r_none.n_swept      # grid multiplies the sweep
