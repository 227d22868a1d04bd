"""Decompose the products-scale device-sampled train step on real TPU.

VERDICT r2 next-step #10: the bench headline (27.4M edges/s/chip at
products scale) sits well below the 128M scan ceiling measured on the
small graph; PERF.md fingers the hop-2 feature gather. This script
measures each component of the step in isolation on the same cached
bench tables so the attack lands on the real bottleneck:

  python tools/profile_device_step.py            # all probes
  python tools/profile_device_step.py --probe gather

Measurement notes:
  - tables ride as jit ARGUMENTS — closing over device arrays bakes
    ~600MB of literals into the HLO;
  - every probe is a lax.scan of SCAN_LEN iterations whose inputs vary
    per iteration (fold_in / index-perturbation), timed as one
    dispatch, and each rep varies the seed argument so no two
    dispatches are identical;
  - the timed sync is jax.block_until_ready on the probe's scalar
    result (jax returns before the device finishes; the block is the
    sync to time around). The `rtt_ms` result is the dispatch+sync
    floor for a trivial program; real probe costs are
    (probe_ms·SCAN_LEN − rtt) / SCAN_LEN ≈ probe_ms for anything
    slower than ~0.5ms/iter.

Writes a JSON summary to stdout (one object per probe).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SCAN_LEN = 16


def _timeit(fn, *args, reps=3):
    """fn(*args, seed) must run SCAN_LEN internally-varied iterations
    and return a SCALAR; returns per-iteration seconds, min over reps
    (each rep gets a fresh seed so no two dispatches are identical).
    Timed around block_until_ready on the result."""
    import jax

    jax.block_until_ready(fn(*args, 0))   # compile + run to completion
    best = float("inf")
    for r in range(1, reps + 1):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, r))
        best = min(best, (time.perf_counter() - t0) / SCAN_LEN)
    return best


def load_tables(cache_dir, nodes, deg, feat, classes, cap):
    key = f"g_n{nodes}_d{deg}_f{feat}_c{classes}_cap{cap}_bf16_v1.npz"
    path = os.path.join(cache_dir, key)
    if not os.path.exists(path):
        raise SystemExit(f"bench cache missing: {path} — run bench.py first")
    z = np.load(path)
    return z["nbr"], z["cum"], z["feat"], z["label"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", default="all",
                    help="all|step|sample|gather|encoder")
    ap.add_argument("--nodes", type=int, default=2_450_000)
    ap.add_argument("--avg_degree", type=int, default=50)
    ap.add_argument("--feat_dim", type=int, default=100)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--cap", type=int, default=32)
    ap.add_argument("--batch", type=int, default=32768)
    ap.add_argument("--fanouts", default="15,10")
    ap.add_argument("--reps", type=int, default=3)
    from euler_tpu.platform import add_platform_flag, init_platform

    add_platform_flag(ap)
    args = ap.parse_args()
    init_platform(args.platform)

    import jax
    import jax.numpy as jnp

    cache = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".bench_cache")
    nbr_h, cum_h, feat_h, label_h = load_tables(
        cache, args.nodes, args.avg_degree, args.feat_dim, args.classes,
        args.cap)
    fanouts = tuple(int(x) for x in args.fanouts.split(","))
    B = args.batch
    N = nbr_h.shape[0] - 1
    nbr = jax.device_put(nbr_h)
    cum = jax.device_put(cum_h)
    feat = jax.device_put(feat_h.astype(np.float32)).astype(jnp.bfloat16)
    label = jax.device_put(label_h.astype(np.float32))
    del nbr_h, cum_h, feat_h
    print(f"# backend={jax.default_backend()} N={N} cap={args.cap} "
          f"feat_dim={feat.shape[1]} B={B} fanouts={fanouts} "
          f"scan_len={SCAN_LEN}", file=sys.stderr)

    from euler_tpu.parallel.device_sampler import (
        sample_fanout_rows, sample_hop,
    )

    key = jax.random.key(7)
    roots = jax.random.randint(key, (B,), 0, N, dtype=jnp.int32)
    results = {}
    results_arrays = {}   # device arrays shared across probe families
    probes = args.probe.split(",")

    def measure(name, fn, *margs, scale=1.0, **kw):
        """Record one probe; a failing probe logs and never loses the
        session's other measurements (each result prints as it lands).
        scale
        multiplies the per-iteration time (for probes that are not a
        SCAN_LEN scan, e.g. the single-dispatch rtt probe)."""
        try:
            results[name] = 1e3 * _timeit(fn, *margs, **kw) * scale
        except Exception as e:  # noqa: BLE001 — probes are best-effort
            results[name + "_error"] = repr(e)[:200]
        print(f"# {name} = {results.get(name, results.get(name + '_error'))}",
              file=sys.stderr, flush=True)

    def want(p):
        return "all" in probes or p in probes

    # dispatch+sync floor: a trivial scalar program through the same
    # timing path, so readers can judge how much of a small probe is
    # dispatch round-trip rather than device work
    measure("rtt_ms", jax.jit(lambda x, seed: x * 1.0 + seed),
            jnp.float32(1), scale=SCAN_LEN, reps=args.reps)

    def scanned(body):
        """body(carry_sum, i, seed) -> value; returns jitted fn running
        SCAN_LEN iterations with a carried dependency."""

        @jax.jit
        def run(*args_and_seed):
            *xs, seed = args_and_seed

            def step(c, i):
                v = body(c, i, seed, *xs)
                return c + v.astype(jnp.float32), None

            out, _ = jax.lax.scan(step, jnp.float32(0),
                                  jnp.arange(SCAN_LEN))
            return out

        return run

    # a cheap per-iteration perturbation keeping rows in [0, N]
    def perturb(rr, i, seed):
        return (rr + (i + 1) * (seed * 131071 % 1000003)) % (N + 1)

    @jax.jit
    def sample_rows(nbr, cum, roots, seed):
        k = jax.random.fold_in(jax.random.key(17), seed)
        return sample_fanout_rows(nbr, cum, roots, fanouts, k)

    rows_all = jax.block_until_ready(sample_rows(nbr, cum, roots, 0))

    # ---- sampling only -------------------------------------------------
    if want("sample"):
        def samp(c, i, seed, nbr, cum, roots):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            rows = sample_fanout_rows(nbr, cum, roots, fanouts, k)
            return sum(r.sum() for r in rows)

        measure("sample_only_ms", scanned(samp), nbr, cum, roots,
                reps=args.reps)

        def hop2(c, i, seed, nbr, cum, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            return sample_hop(nbr, cum, perturb(r1, i, seed),
                              fanouts[1], k).sum()

        measure("sample_hop2_ms", scanned(hop2), nbr, cum, rows_all[1],
                reps=args.reps)

        # sorted-locality variant: sort the hop-1 frontier before the
        # cum-row gather so the 491k random rows arrive in ascending
        # order (sort cost included in the probe — the lever only wins
        # if sort + local gathers beat the random gathers)
        def hop2s(c, i, seed, nbr, cum, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            r = jnp.sort(perturb(r1, i, seed))
            return sample_hop(nbr, cum, r, fanouts[1], k).sum()

        measure("sample_hop2_sorted_ms", scanned(hop2s), nbr, cum,
                rows_all[1], reps=args.reps)

        # flat-pick baseline: the RETIRED neighbor-pick algorithm (one
        # n·count single-element gather), pinned inline so the A/B
        # against the live count-aware row pick stays measurable after
        # the round-5 flip. sample_hop2_ms above times the LIVE path
        # (count=10 >= 4 → row gather + take_along_axis, measured
        # 90.0ms); this baseline measured 95.9ms in the same window —
        # gather cost on this chip is element-count-bound, not
        # byte-bound (scalar_gather_h2_ms 77.9 vs cum_gather_h1rows_ms
        # 21.7 for the same node count). Distinct from the fused
        # [N+1,2C] layout, whose single 256B-row gather is SLOWER
        # (sample_hop2_fused_ms 110.3).
        def hop2fp(c, i, seed, nbr, cum, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            r = perturb(r1, i, seed)
            C = nbr.shape[1]
            cumr = jnp.take(cum, r, axis=0)
            total = cumr[:, -1]
            u = jax.random.uniform(k, (r.shape[0], fanouts[1])) \
                * total[:, None]
            col = (cumr[:, None, :] <= u[:, :, None]).sum(-1)
            col = jnp.clip(col, 0, C - 1).astype(jnp.int32)
            flat = r[:, None] * C + col
            return jnp.take(nbr.reshape(-1), flat.reshape(-1)).sum()

        measure("sample_hop2_flatpick_ms", scanned(hop2fp), nbr, cum,
                rows_all[1], reps=args.reps)

        # fused layout: one [N+1, 2C] i32 table, one gather per hop
        from euler_tpu.parallel.device_sampler import (
            fuse_tables, sample_fanout_rows_fused, sample_hop_fused,
        )

        fused = jax.block_until_ready(
            jax.jit(fuse_tables)(nbr, cum))

        def sampf(c, i, seed, fused, roots):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            rows = sample_fanout_rows_fused(fused, roots, fanouts, k)
            return sum(r.sum() for r in rows)

        measure("sample_only_fused_ms", scanned(sampf), fused, roots,
                reps=args.reps)

        def hop2f(c, i, seed, fused, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            return sample_hop_fused(fused, perturb(r1, i, seed),
                                    fanouts[1], k).sum()

        measure("sample_hop2_fused_ms", scanned(hop2f), fused, rows_all[1],
                reps=args.reps)
        del fused

        # ---- round-6 tentpole: O(1) alias-method draws. The alias row
        # gather matches the cum-row gather's element count (gathers are
        # element-count-bound on this chip), but the per-draw work drops
        # from a C-wide inverse-CDF scan to one packed-word read —
        # compare sample_hop2_alias_ms against the pinned
        # sample_hop2_flatpick_ms baseline and the live sample_hop2_ms.
        from euler_tpu.parallel.device_sampler import build_alias_tables

        alias_tab = jax.device_put(build_alias_tables(
            np.asarray(nbr), cum_tab=np.asarray(cum)))

        def hop2a(c, i, seed, nbr, cum, alias_tab, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            return sample_hop(nbr, cum, perturb(r1, i, seed),
                              fanouts[1], k, alias_table=alias_tab).sum()

        measure("sample_hop2_alias_ms", scanned(hop2a), nbr, cum,
                alias_tab, rows_all[1], reps=args.reps)

        def sampa(c, i, seed, nbr, cum, alias_tab, roots):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            rows = sample_fanout_rows(nbr, cum, roots, fanouts, k,
                                      alias_table=alias_tab)
            return sum(r.sum() for r in rows)

        measure("sample_only_alias_ms", scanned(sampa), nbr, cum,
                alias_tab, roots, reps=args.reps)

        # walk-chain A/B: the walk family's chained count=1 draws are
        # where the O(1) constant compounds (walk_len sequential draws
        # per step, each on the flat-pick side of the count-aware
        # split). Same chain through the live weighted path vs alias.
        WALK_CHAIN = 5

        def wchain(c, i, seed, nbr, cum, roots):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            cur = perturb(roots, i, seed)
            tot = jnp.float32(0)
            for _ in range(WALK_CHAIN):
                k, sub = jax.random.split(k)
                cur = sample_hop(nbr, cum, cur, 1, sub)
                tot = tot + cur.sum().astype(jnp.float32)
            return tot

        measure("walk_chain_ms", scanned(wchain), nbr, cum, roots,
                reps=args.reps)

        def wchain_a(c, i, seed, nbr, cum, alias_tab, roots):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            cur = perturb(roots, i, seed)
            tot = jnp.float32(0)
            for _ in range(WALK_CHAIN):
                k, sub = jax.random.split(k)
                cur = sample_hop(nbr, cum, cur, 1, sub,
                                 alias_table=alias_tab)
                tot = tot + cur.sum().astype(jnp.float32)
            return tot

        measure("walk_chain_alias_ms", scanned(wchain_a), nbr, cum,
                alias_tab, roots, reps=args.reps)
        del alias_tab

        # ---- round-5 third-window candidates: RNG cost + uniform path.
        # The bench graph (and cora/pubmed/products) is UNWEIGHTED, so
        # per-row uniform weights make the cum-row gather removable: the
        # pad convention (pad slots hold pad_row) means degree is
        # derivable from the neighbor row itself, (row != pad).sum(-1) —
        # C compares on data the gather already brought into VMEM. One
        # row gather per hop instead of two, and the inverse-CDF compare
        # collapses to floor(u·deg).
        n2, k2_ = rows_all[1].shape[0], fanouts[1]

        def rngu(c, i, seed):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            return jax.random.uniform(k, (n2, k2_)).sum()

        measure("rng_uniform_h2_ms", scanned(rngu), reps=args.reps)

        def rngu_rbg(c, i, seed):
            k = jax.random.fold_in(
                jax.random.key(17, impl="rbg"), seed * 1000 + i)
            return jax.random.uniform(k, (n2, k2_)).sum()

        measure("rng_uniform_h2_rbg_ms", scanned(rngu_rbg), reps=args.reps)

        def _hop_unif(nbr, r, k, count):
            row = jnp.take(nbr, r, axis=0)                     # [n, C]
            pad = nbr.shape[0] - 1
            deg = (row != pad).sum(-1).astype(jnp.float32)     # [n]
            u = jax.random.uniform(k, (r.shape[0], count))
            col = jnp.minimum((u * deg[:, None]).astype(jnp.int32),
                              jnp.maximum(deg[:, None].astype(jnp.int32)
                                          - 1, 0))
            return jnp.take_along_axis(row, col, axis=1)

        def hop2u(c, i, seed, nbr, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            return _hop_unif(nbr, perturb(r1, i, seed), k, k2_).sum()

        measure("sample_hop2_unif_ms", scanned(hop2u), nbr, rows_all[1],
                reps=args.reps)

        def hop2u_rbg(c, i, seed, nbr, r1):
            k = jax.random.fold_in(
                jax.random.key(17, impl="rbg"), seed * 1000 + i)
            return _hop_unif(nbr, perturb(r1, i, seed), k, k2_).sum()

        measure("sample_hop2_unif_rbg_ms", scanned(hop2u_rbg), nbr,
                rows_all[1], reps=args.reps)

        # live weighted path with an rbg key: isolates how much of the
        # live hop-2 cost is threefry itself
        def hop2_rbg(c, i, seed, nbr, cum, r1):
            k = jax.random.fold_in(
                jax.random.key(17, impl="rbg"), seed * 1000 + i)
            return sample_hop(nbr, cum, perturb(r1, i, seed),
                              fanouts[1], k).sum()

        measure("sample_hop2_rbg_ms", scanned(hop2_rbg), nbr, cum,
                rows_all[1], reps=args.reps)

        # full 2-hop fanout, uniform path + rbg: the end-to-end sampling
        # candidate (compare with sample_only_ms)
        def sampu(c, i, seed, nbr, roots):
            k = jax.random.fold_in(
                jax.random.key(17, impl="rbg"), seed * 1000 + i)
            cur = roots
            tot = jnp.float32(0)
            for kk in fanouts:
                k, sub = jax.random.split(k)
                cur = _hop_unif(nbr, cur, sub, kk).reshape(-1)
                tot = tot + cur.sum().astype(jnp.float32)
            return tot

        measure("sample_only_unif_rbg_ms", scanned(sampu), nbr, roots,
                reps=args.reps)

        # ---- the pick itself: on-chip, take_along_axis over [n, C]
        # rows lowers to an n·count-element gather — element-count-bound
        # like the retired flat pick. Candidate replacement: a masked
        # sum over the C lanes, (row · (iota == col)).sum(-1) — pure
        # fused VPU work on data the row gather already staged, no
        # gather at all. Ids ride f32 exactly (N < 2^24).
        def _pick_onehot(row, col):
            C = row.shape[1]
            iota = jnp.arange(C, dtype=jnp.int32)
            ind = iota[None, None, :] == col[:, :, None]   # [n, k, C]
            return (row[:, None, :].astype(jnp.float32)
                    * ind).sum(-1).astype(jnp.int32)       # [n, k]

        def _hop_unif_oh(nbr, r, k, count):
            row = jnp.take(nbr, r, axis=0)
            pad = nbr.shape[0] - 1
            deg = (row != pad).sum(-1).astype(jnp.float32)
            u = jax.random.uniform(k, (r.shape[0], count))
            col = jnp.minimum((u * deg[:, None]).astype(jnp.int32),
                              jnp.maximum(deg[:, None].astype(jnp.int32)
                                          - 1, 0))
            return _pick_onehot(row, col)

        def hop2u_oh(c, i, seed, nbr, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            return _hop_unif_oh(nbr, perturb(r1, i, seed), k, k2_).sum()

        measure("sample_hop2_unif_onehot_ms", scanned(hop2u_oh), nbr,
                rows_all[1], reps=args.reps)

        # weighted path, same pick swap: cum+nbr gathers stay, only
        # take_along_axis is replaced (compare with sample_hop2_ms)
        def hop2_oh(c, i, seed, nbr, cum, r1):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            r = perturb(r1, i, seed)
            C = nbr.shape[1]
            cumr = jnp.take(cum, r, axis=0)
            total = cumr[:, -1]
            u = jax.random.uniform(k, (r.shape[0], k2_)) * total[:, None]
            col = (cumr[:, None, :] <= u[:, :, None]).sum(-1)
            col = jnp.clip(col, 0, C - 1).astype(jnp.int32)
            row = jnp.take(nbr, r, axis=0)
            return _pick_onehot(row, col).sum()

        measure("sample_hop2_onehot_ms", scanned(hop2_oh), nbr, cum,
                rows_all[1], reps=args.reps)

        # end-to-end 2-hop fanout, uniform + onehot pick (the full
        # candidate sampling path; compare with sample_only_ms)
        def sampu_oh(c, i, seed, nbr, roots):
            k = jax.random.fold_in(jax.random.key(17), seed * 1000 + i)
            cur = roots
            tot = jnp.float32(0)
            for kk in fanouts:
                k, sub = jax.random.split(k)
                cur = _hop_unif_oh(nbr, cur, sub, kk).reshape(-1)
                tot = tot + cur.sum().astype(jnp.float32)
            return tot

        measure("sample_only_unif_onehot_ms", scanned(sampu_oh), nbr,
                roots, reps=args.reps)

    # ---- feature gathers ----------------------------------------------
    if want("gather"):
        def mk_gather(post=None):
            def g(c, i, seed, tab, rr):
                r = perturb(rr, i, seed)
                if post is not None:
                    r = post(r)
                return jnp.take(tab, r, axis=0).sum()
            return g

        for h, r in enumerate(rows_all):
            measure(f"feat_gather_h{h}_ms",
                    scanned(mk_gather()), feat, r, reps=args.reps)
            results[f"feat_gather_h{h}_rows"] = int(r.shape[0])
        r2 = rows_all[-1]
        measure("feat_gather_h2_sortin_ms", scanned(mk_gather(jnp.sort)),
                feat, r2, reps=args.reps)

        # fused gather+mean (what the encoder actually consumes)
        k2 = fanouts[-1]

        def gmean(c, i, seed, tab, rr):
            x = jnp.take(tab, perturb(rr, i, seed), axis=0)
            return x.reshape(-1, k2, tab.shape[1]).mean(axis=1).sum()

        measure("feat_gathermean_h2_ms", scanned(gmean), feat, r2,
                reps=args.reps)

        # sorted gather + segment-mean: the END-TO-END sorted-locality
        # candidate. The feature rows are gathered in ascending-id order
        # (HBM locality) and the permutation is absorbed by the segment
        # ids of the aggregation — no un-permute gather of the gathered
        # rows. Wins only if argsort(4.9M) + local gathers + scatter-add
        # beat random gathers + reshape-mean; compare with
        # feat_gathermean_h2_ms.
        def gmean_sorted(c, i, seed, tab, rr):
            r = perturb(rr, i, seed)
            # one key-value sort yields sorted rows AND the permutation
            # (argsort + take(r, order) would pay a second 4.9M gather)
            r_sorted, orig_pos = jax.lax.sort_key_val(
                r, jnp.arange(r.shape[0], dtype=jnp.int32))
            x = jnp.take(tab, r_sorted, axis=0)
            seg = orig_pos // k2
            s = jax.ops.segment_sum(x, seg,
                                    num_segments=r.shape[0] // k2)
            return (s * (1.0 / k2)).sum()

        measure("feat_gathermean_h2_sorted_ms", scanned(gmean_sorted),
                feat, r2, reps=args.reps)
        # cum-table row gather at hop-1 scale (sampling's own gather)
        measure("cum_gather_h1rows_ms", scanned(mk_gather()), cum,
                rows_all[1], reps=args.reps)

        # scalar gather (sample_hop's neighbor lookup at hop 2)
        cols = jax.random.randint(key, (rows_all[1].shape[0] * k2,), 0,
                                  args.cap, dtype=jnp.int32)

        def scal(c, i, seed, nbr, rr, cols):
            fl = jnp.repeat(perturb(rr, i, seed), k2) * args.cap + cols
            return jnp.take(nbr.reshape(-1), fl).sum()

        measure("scalar_gather_h2_ms", scanned(scal), nbr, rows_all[1],
                cols, reps=args.reps)

        # pad-to-128-lanes helper shared by the pad/int8+pad/pallas-pad
        # probes below (feat_dim ≤ 128 is a probe precondition)
        def pad128(tab):
            return jax.block_until_ready(jax.jit(
                lambda f: jnp.pad(f, ((0, 0),
                                      (0, 128 - f.shape[1]))))(tab))

        # lane-padded feature table: 100 → 128 dims so each gathered row
        # is one aligned 256B tile
        featp = pad128(feat)
        measure("feat_gather_h2_pad128_ms", scanned(mk_gather()), featp,
                r2, reps=args.reps)

        # gmean reads k2/tab.shape[1] inside the body — reuse it
        measure("feat_gathermean_h2_pad128_ms", scanned(gmean), featp, r2,
                reps=args.reps)
        del featp

        # promise_in_bounds: skip the clamp/oob handling in the gather
        # (jnp.take has no such mode; it lives on the .at[] indexing API)
        def g_pib(c, i, seed, tab, rr):
            return tab.at[perturb(rr, i, seed)].get(
                mode="promise_in_bounds").sum()

        measure("feat_gather_h2_pib_ms", scanned(g_pib), feat, r2,
                reps=args.reps)

        # int8-quantized table (DeviceFeatureStore(quantize='int8')):
        # half the gather bytes, dequant fused into the consumer
        from euler_tpu.parallel.feature_store import quantize_int8

        q_h, scale_h = quantize_int8(np.asarray(
            feat.astype(jnp.float32)))
        featq = results_arrays["featq_cached"] = jax.device_put(q_h)
        fscale = results_arrays["fscale_cached"] = jax.device_put(
            scale_h.astype(np.float32))
        del q_h

        def g_q(c, i, seed, tab, sc, rr):
            x = jnp.take(tab, perturb(rr, i, seed), axis=0)
            return (x.astype(jnp.bfloat16) * sc.astype(jnp.bfloat16)).sum()

        measure("feat_gather_h2_int8_ms", scanned(g_q), featq, fscale,
                r2, reps=args.reps)

        def gmean_q(c, i, seed, tab, sc, rr):
            x = jnp.take(tab, perturb(rr, i, seed), axis=0)
            x = x.astype(jnp.bfloat16) * sc.astype(jnp.bfloat16)
            return x.reshape(-1, k2, tab.shape[1]).mean(axis=1).sum()

        measure("feat_gathermean_h2_int8_ms", scanned(gmean_q), featq,
                fscale, r2, reps=args.reps)

        # int8 + 128-lane pad: one 128-byte-aligned row per gather — the
        # alignment question that matters under the round-4 int8-on
        # default (pad alone was probed on the bf16 table above)
        featqp = pad128(featq)
        fscalep = jax.device_put(np.pad(
            scale_h.astype(np.float32), (0, 128 - scale_h.shape[0]),
            constant_values=1.0))
        measure("feat_gather_h2_int8_pad128_ms", scanned(g_q), featqp,
                fscalep, r2, reps=args.reps)
        measure("feat_gathermean_h2_int8_pad128_ms", scanned(gmean_q),
                featqp, fscalep, r2, reps=args.reps)
        del featqp
        del featq

        # fused pallas gather+mean kernel (ops/pallas_ops.py) against the
        # XLA formulation over the same table. The kernel takes float32
        # tables of 128-lane rows only (Mosaic refuses the rest:
        # pallas_ops._check_kernel_shapes), so both read an f32 pad128
        # table
        from euler_tpu.ops.pallas_ops import _pallas_gather_mean

        featp32 = pad128(feat).astype(jnp.float32)
        measure("feat_gathermean_h2_f32_pad128_ms", scanned(gmean),
                featp32, r2, reps=args.reps)
        # (tile_n output rows per grid step, semaphore layout) — the
        # shapes that compile for a described v5e: per-copy semaphores
        # exhaust semaphore memory at tile 128, one shared one does not
        for tile, one_sem in ((8, False), (32, False), (32, True),
                              (128, True)):
            def gm_pallas(c, i, seed, tab, rr, _tile=tile, _one=one_sem):
                r = perturb(rr, i, seed).reshape(-1, k2)
                return _pallas_gather_mean(tab, r, tile_n=_tile,
                                           one_sem=_one).sum()

            measure(f"feat_gathermean_h2_pallas_t{tile}"
                    f"{'_onesem' if one_sem else ''}_ms",
                    scanned(gm_pallas), featp32, r2, reps=args.reps)
        del featp32

    # ---- encoder fwd+bwd on fixed layers --------------------------------
    if want("encoder"):
        from euler_tpu.utils.encoders import SageEncoder

        gj = jax.jit(lambda tab, rr: jnp.take(tab, rr, axis=0))
        layers = [jax.block_until_ready(gj(feat, r)) for r in rows_all]
        enc = SageEncoder(128, fanouts, "mean")
        p0 = enc.init(jax.random.key(0), layers)

        def loss_fn(p, layers):
            return (enc.apply(p, layers).astype(jnp.float32) ** 2).mean()

        def encfb(c, i, seed, p0, *layers):
            # perturb layer 0 so each iteration's grads differ
            l0 = layers[0] + (i * seed).astype(jnp.bfloat16)
            l, g = jax.value_and_grad(loss_fn)(
                p0, [l0, *layers[1:]])
            return l + sum(jnp.sum(x).astype(jnp.float32)
                           for x in jax.tree.leaves(g))

        measure("encoder_fb_ms", scanned(encfb), p0, *layers,
                reps=args.reps)

    # ---- full step ------------------------------------------------------
    if want("step"):
        import optax

        from euler_tpu.models import DeviceSampledGraphSage

        model = DeviceSampledGraphSage(
            num_classes=args.classes, multilabel=False, dim=128,
            fanouts=fanouts)
        batch0 = {"rows": [roots], "sample_seed": jnp.int32(0),
                  "nbr_table": nbr, "cum_table": cum,
                  "feature_table": feat,
                  "labels": jax.jit(
                      lambda l, r: jnp.take(l, r, axis=0))(label, roots)}
        params = model.init(jax.random.key(0), batch0)
        tx = optax.adam(1e-2)
        opt0 = tx.init(params)

        def loss_fn(p, batch):
            return model.apply(p, batch).loss

        @jax.jit
        def run_steps(params, opt, nbr, cum, feat, label, roots, seed):
            def step(carry, i):
                p, o = carry
                r = perturb(roots, i, seed)
                batch = {"rows": [r], "sample_seed": seed * 1000 + i,
                         "nbr_table": nbr, "cum_table": cum,
                         "feature_table": feat,
                         "labels": jnp.take(label, r, axis=0)}
                l, g = jax.value_and_grad(loss_fn)(p, batch)
                up, o = tx.update(g, o, p)
                return (optax.apply_updates(p, up), o), l

            (p, o), ls = jax.lax.scan(step, (params, opt),
                                      jnp.arange(SCAN_LEN))
            return ls.sum()

        measure("full_step_ms", run_steps, params, opt0, nbr, cum,
                feat, label, roots, reps=args.reps)
        epe = B * (fanouts[0] + fanouts[0] * fanouts[1])
        if "full_step_ms" in results:
            results["full_step_edges_per_sec"] = round(
                epe / (results["full_step_ms"] / 1e3))
            results["full_step_nodes_per_sec"] = round(
                B / (results["full_step_ms"] / 1e3))

        # same step over the fused sampling table
        from euler_tpu.parallel.device_sampler import fuse_tables

        fused = jax.block_until_ready(jax.jit(fuse_tables)(nbr, cum))

        @jax.jit
        def run_steps_fused(params, opt, fused, feat, label, roots, seed):
            def step(carry, i):
                p, o = carry
                r = perturb(roots, i, seed)
                batch = {"rows": [r], "sample_seed": seed * 1000 + i,
                         "nbrcum_table": fused,
                         "feature_table": feat,
                         "labels": jnp.take(label, r, axis=0)}
                l, g = jax.value_and_grad(loss_fn)(p, batch)
                up, o = tx.update(g, o, p)
                return (optax.apply_updates(p, up), o), l

            (p, o), ls = jax.lax.scan(step, (params, opt),
                                      jnp.arange(SCAN_LEN))
            return ls.sum()

        measure("full_step_fused_ms", run_steps_fused, params, opt0,
                fused, feat, label, roots, reps=args.reps)
        if "full_step_fused_ms" in results:
            results["full_step_fused_edges_per_sec"] = round(
                epe / (results["full_step_fused_ms"] / 1e3))

        # fused sampling table + int8 feature table together — the
        # combination bench.py --fused_sampler --int8_features runs.
        # reuse the gather probe's quantization when it already ran
        # (the fp32 round-trip of the full table costs real minutes)
        if "featq_cached" not in results_arrays:
            from euler_tpu.parallel.feature_store import quantize_int8

            q_h, scale_h = quantize_int8(
                np.asarray(feat.astype(jnp.float32)))
            results_arrays["featq_cached"] = jax.device_put(q_h)
            results_arrays["fscale_cached"] = jax.device_put(scale_h)
            del q_h
        featq = results_arrays["featq_cached"]
        fscale = results_arrays["fscale_cached"].astype(jnp.bfloat16)

        @jax.jit
        def run_steps_fused_q(params, opt, fused, featq, fscale, label,
                              roots, seed):
            def step(carry, i):
                p, o = carry
                r = perturb(roots, i, seed)
                batch = {"rows": [r], "sample_seed": seed * 1000 + i,
                         "nbrcum_table": fused,
                         "feature_table": featq, "feature_scale": fscale,
                         "labels": jnp.take(label, r, axis=0)}
                l, g = jax.value_and_grad(loss_fn)(p, batch)
                up, o = tx.update(g, o, p)
                return (optax.apply_updates(p, up), o), l

            (p, o), ls = jax.lax.scan(step, (params, opt),
                                      jnp.arange(SCAN_LEN))
            return ls.sum()

        measure("full_step_fused_int8_ms", run_steps_fused_q, params,
                opt0, fused, featq, fscale, label, roots, reps=args.reps)
        if "full_step_fused_int8_ms" in results:
            results["full_step_fused_int8_edges_per_sec"] = round(
                epe / (results["full_step_fused_int8_ms"] / 1e3))

        # split-chain variant: the batch processed as two independent
        # half-chains (sample→gather→encode), losses averaged — the
        # chains share no deps, so XLA may overlap one half's gathers
        # with the other half's MXU work
        @jax.jit
        def run_steps_split(params, opt, nbr, cum, feat, label, roots,
                            seed):
            half = roots.shape[0] // 2

            # defined INSIDE the jit so nbr/cum/feat resolve to the jit
            # arguments, not the main-scope device arrays (closing over
            # those bakes ~1GB of tables into the HLO as literals)
            def loss_half(p, half_roots, seed_arr, labels_half):
                batch = {"rows": [half_roots], "sample_seed": seed_arr,
                         "nbr_table": nbr, "cum_table": cum,
                         "feature_table": feat, "labels": labels_half}
                return model.apply(p, batch).loss

            def step(carry, i):
                p, o = carry
                r = perturb(roots, i, seed)
                lab = jnp.take(label, r, axis=0)

                def loss_fn2(p):
                    l1 = loss_half(p, r[:half], seed * 2000 + 2 * i,
                                   lab[:half])
                    l2 = loss_half(p, r[half:], seed * 2000 + 2 * i + 1,
                                   lab[half:])
                    return 0.5 * (l1 + l2)

                l, g = jax.value_and_grad(loss_fn2)(p)
                up, o = tx.update(g, o, p)
                return (optax.apply_updates(p, up), o), l

            (p, o), ls = jax.lax.scan(step, (params, opt),
                                      jnp.arange(SCAN_LEN))
            return ls.sum()

        measure("full_step_split2_ms", run_steps_split, params, opt0,
                nbr, cum, feat, label, roots, reps=args.reps)
        if "full_step_split2_ms" in results:
            results["full_step_split2_edges_per_sec"] = round(
                epe / (results["full_step_split2_ms"] / 1e3))

        # historical-activation config (bench --act_cache, int8
        # features): the round-5 structural candidate — per-step gather
        # rows drop from B·(1+k1+k1·k2) to B·(1+2·k1). Compare by
        # nodes/s (it aggregates fewer edges by design); the
        # full_step_* nodes/s equivalents are B/step_ms.
        from euler_tpu.models import DeviceSampledScalableSage

        # featq/fscale are in scope from the fused_int8 probe above
        sc_model = DeviceSampledScalableSage(
            num_classes=args.classes, multilabel=False, dim=128,
            fanout=fanouts[0], num_layers=len(fanouts),
            max_id=N, cache_dtype=jnp.bfloat16)
        batch0c = {"rows": [roots], "sample_seed": jnp.int32(0),
                   "nbr_table": nbr, "cum_table": cum,
                   "feature_table": featq, "feature_scale": fscale,
                   "labels": jax.jit(
                       lambda l, r: jnp.take(l, r, axis=0))(label, roots)}
        vars_c = sc_model.init(jax.random.key(0), batch0c)
        params_c, cache0 = vars_c["params"], vars_c["cache"]
        opt0c = tx.init(params_c)

        @jax.jit
        def run_steps_cache(params, opt, cache, nbr, cum, featq, fscale,
                            label, roots, seed):
            def step(carry, i):
                p, o, ch = carry
                r = perturb(roots, i, seed)
                batch = {"rows": [r], "sample_seed": seed * 1000 + i,
                         "nbr_table": nbr, "cum_table": cum,
                         "feature_table": featq, "feature_scale": fscale,
                         "labels": jnp.take(label, r, axis=0)}

                def loss_c(pp):
                    out, new = sc_model.apply(
                        {"params": pp, "cache": ch}, batch,
                        mutable=["cache"])
                    return out.loss, new["cache"]

                (l, ch), g = jax.value_and_grad(
                    loss_c, has_aux=True)(p)
                up, o = tx.update(g, o, p)
                return (optax.apply_updates(p, up), o, ch), l

            (p, o, ch), ls = jax.lax.scan(step, (params, opt, cache),
                                          jnp.arange(SCAN_LEN))
            return ls.sum()

        measure("full_step_cache_int8_ms", run_steps_cache, params_c,
                opt0c, cache0, nbr, cum, featq, fscale, label, roots,
                reps=args.reps)
        if "full_step_cache_int8_ms" in results:
            results["full_step_cache_int8_nodes_per_sec"] = round(
                B / (results["full_step_cache_int8_ms"] / 1e3))

    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
