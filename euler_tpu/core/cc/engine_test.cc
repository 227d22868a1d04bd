// Native engine self-test binary — assert-style unit tests over the C++
// core, runnable standalone and under sanitizers:
//
//   make test        # build + run (O2)
//   make asan        # AddressSanitizer build + run
//   make tsan        # ThreadSanitizer build + run (race detection — the
//                    # CI the reference lacked, SURVEY.md §5)
//
// Mirrors the reference's gtest tiers (SURVEY.md §4): common (samplers,
// threadpool, rng), graph store, serde, executor, index, compiler.
#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <thread>
#include <csignal>
#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <string>
#include <vector>

#include "common.h"
#include "dag.h"
#include "gql.h"
#include "graph.h"
#include "index.h"
#include "io.h"
#include "kernels_common.h"
#include "rpc.h"
#include "sampling.h"
#include "serde.h"
#include "store.h"
#include "tensor.h"
#include "threadpool.h"
#include "udf.h"
#include "wal.h"

namespace et {
namespace {

int g_failures = 0;

#define CHECK_TRUE(cond)                                              \
  do {                                                                \
    if (!(cond)) {                                                    \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__,    \
                   #cond);                                            \
      ++g_failures;                                                   \
    }                                                                 \
  } while (0)

#define CHECK_OK(expr)                                                \
  do {                                                                \
    ::et::Status _s = (expr);                                         \
    if (!_s.ok()) {                                                   \
      std::fprintf(stderr, "FAIL %s:%d: %s -> %s\n", __FILE__,        \
                   __LINE__, #expr, _s.message().c_str());            \
      ++g_failures;                                                   \
    }                                                                 \
  } while (0)

// ---- common: rng, samplers, threadpool ----
void TestPcg32Determinism() {
  Pcg32 a(42, 1), b(42, 1), c(43, 1);
  bool same = true, diff = false;
  for (int i = 0; i < 100; ++i) {
    uint32_t x = a.NextU32(), y = b.NextU32(), z = c.NextU32();
    same &= (x == y);
    diff |= (x != z);
  }
  CHECK_TRUE(same);
  CHECK_TRUE(diff);
}

void TestAliasSamplerStatistics() {
  // weights 1,2,3,4 → frequencies ∝ weight (statistical test like the
  // reference's fast_weighted_collection_test.cc)
  std::vector<float> w{1, 2, 3, 4};
  AliasSampler s;
  s.Init(w);
  Pcg32 rng(7);
  std::vector<int> counts(4, 0);
  const int N = 200000;
  for (int i = 0; i < N; ++i) counts[s.Sample(&rng)]++;
  for (int i = 0; i < 4; ++i) {
    double expect = N * w[i] / 10.0;
    CHECK_TRUE(std::fabs(counts[i] - expect) < 5 * std::sqrt(expect));
  }
}

void TestParallelForCoversAll() {
  std::vector<std::atomic<int>> hits(10000);
  ParallelFor(GlobalThreadPool(), 10000, 64,
              [&](int64_t b, int64_t e, int) {
                for (int64_t i = b; i < e; ++i) hits[i].fetch_add(1);
              });
  for (auto& h : hits) CHECK_TRUE(h.load() == 1);
}

void TestThreadPoolStress() {
  // many tiny tasks racing on an atomic — trips TSAN if the queue or
  // latch were racy
  std::atomic<int64_t> sum{0};
  ThreadPool pool(8);
  std::atomic<int> remaining{10000};
  std::mutex mu;
  std::condition_variable cv;
  for (int i = 0; i < 10000; ++i) {
    pool.Schedule([&, i] {
      sum.fetch_add(i);
      // decrement under mu: if the decrement were outside, the main
      // thread could observe 0 and destroy mu/cv while this worker is
      // about to lock them (UB caught by review r4)
      std::lock_guard<std::mutex> lk(mu);
      if (remaining.fetch_sub(1) == 1) cv.notify_one();
    });
  }
  std::unique_lock<std::mutex> lk(mu);
  cv.wait(lk, [&] { return remaining.load() == 0; });
  CHECK_TRUE(sum.load() == 10000LL * 9999 / 2);
}

void TestThreadPoolPriorityLanes() {
  // Both workers of a 2-thread pool get parked on long LOW tasks, six
  // more LOW tasks queue behind them, then one HIGH task arrives. The
  // high-preferring worker (idx 1) must take the HIGH task as soon as
  // it frees — ahead of the whole queued LOW backlog — while worker 0
  // keeps draining LOW (the anti-starvation guarantee).
  ThreadPool pool(2);
  std::mutex mu;
  std::condition_variable cv;
  int low_done = 0;
  bool high_done = false;
  int low_done_at_high = -1;
  std::atomic<bool> gate{false};
  for (int i = 0; i < 8; ++i) {
    pool.Schedule(
        [&] {
          // first two occupy the workers until the HIGH task is queued
          while (!gate.load()) ::usleep(500);
          ::usleep(5000);
          std::lock_guard<std::mutex> lk(mu);
          ++low_done;
          cv.notify_all();
        },
        ThreadPool::kLow);
  }
  pool.Schedule([&] {
    std::lock_guard<std::mutex> lk(mu);
    high_done = true;
    low_done_at_high = low_done;
    cv.notify_all();
  });  // default lane: kHigh
  gate.store(true);
  std::unique_lock<std::mutex> lk(mu);
  cv.wait(lk, [&] { return high_done; });
  // the high task may wait for ONE in-flight low per worker, never for
  // the queued backlog (6 lows were still queued when it arrived)
  CHECK_TRUE(low_done_at_high <= 4);
  cv.wait(lk, [&] { return low_done == 8; });  // lanes both drain
}

// ---- graph store ----
std::unique_ptr<Graph> RingGraph() {
  GraphBuilder b;
  for (uint64_t i = 1; i <= 10; ++i)
    b.AddNode(i, static_cast<int32_t>(i % 2), static_cast<float>(i));
  for (uint64_t i = 1; i <= 10; ++i)
    b.AddEdge(i, i % 10 + 1, 0, 1.0f);
  b.mutable_meta()->node_features.push_back(
      {"f", FeatureKind::kDense, 2});
  for (uint64_t i = 1; i <= 10; ++i) {
    float v[2] = {static_cast<float>(i), -static_cast<float>(i)};
    b.SetNodeDense(i, 0, v, 2);
  }
  return b.Finalize();
}

void TestGraphStore() {
  auto g = RingGraph();
  CHECK_TRUE(g->node_count() == 10);
  CHECK_TRUE(g->edge_count() == 10);
  Pcg32 rng(1);
  NodeId nb;
  float w;
  int32_t t;
  g->SampleNeighbor(4, nullptr, 0, 1, 0, &rng, &nb, &w, &t);
  CHECK_TRUE(nb == 5);
  float f[2];
  NodeId id = 7;
  g->GetDenseFeature(&id, 1, 0, 2, f);
  CHECK_TRUE(f[0] == 7.0f && f[1] == -7.0f);
  // unknown id zero-fills
  id = 999;
  g->GetDenseFeature(&id, 1, 0, 2, f);
  CHECK_TRUE(f[0] == 0.0f && f[1] == 0.0f);
}

void TestConcurrentSampling() {
  // immutable graph + per-thread rngs: concurrent readers must be clean
  // under TSAN
  auto g = RingGraph();
  ThreadPool pool(8);
  std::atomic<int> remaining{64};
  std::atomic<bool> ok{true};
  std::mutex mu;
  std::condition_variable cv;
  for (int t0 = 0; t0 < 64; ++t0) {
    pool.Schedule([&, t0] {
      Pcg32 rng(t0);
      NodeId out[8];
      g->SampleNode(-1, 8, &rng, out);
      for (NodeId id : out)
        if (id < 1 || id > 10) ok.store(false);
      std::lock_guard<std::mutex> lk(mu);  // see TestThreadPoolStress
      if (remaining.fetch_sub(1) == 1) cv.notify_one();
    });
  }
  std::unique_lock<std::mutex> lk(mu);
  cv.wait(lk, [&] { return remaining.load() == 0; });
  CHECK_TRUE(ok.load());
}

void TestUdfResultCacheConcurrent() {
  // the UDF result cache is hit from the executor's thread pool: hammer
  // Get/Put/Clear/SetCapacity from many threads under TSAN; then check
  // the single-threaded contract (hit returns the stored column,
  // collision-by-construction verifies as a miss).
  auto& c = UdfResultCache::Instance();
  c.SetCapacityBytes(1u << 20);
  c.Clear();
  ThreadPool pool(8);
  std::atomic<int> remaining{64};
  std::mutex mu;
  std::condition_variable cv;
  for (int t0 = 0; t0 < 64; ++t0) {
    pool.Schedule([&, t0] {
      std::vector<uint64_t> ids = {static_cast<uint64_t>(t0 % 8)};
      uint64_t key = UdfCacheKey(1, 0, "udf:mean", 0, ids.data(), 1);
      auto hit = c.Get(key, 1, 0, "udf:mean", 0, ids.data(), 1);
      if (!hit) {
        auto col = std::make_shared<CachedColumn>();
        col->graph_uid = 1;
        col->generation = 0;
        col->spec = "udf:mean";
        col->fid = 0;
        col->ids = ids;
        col->offs = {0, 1};
        col->vals = {static_cast<float>(t0 % 8)};
        c.Put(key, std::move(col));
      }
      if (t0 % 16 == 3) c.Clear();
      if (t0 % 16 == 7) c.SetCapacityBytes(1u << 19);
      std::lock_guard<std::mutex> lk(mu);  // see TestThreadPoolStress
      if (remaining.fetch_sub(1) == 1) cv.notify_one();
    });
  }
  {
    std::unique_lock<std::mutex> lk(mu);
    cv.wait(lk, [&] { return remaining.load() == 0; });
  }
  // single-threaded contract
  c.SetCapacityBytes(1u << 20);
  c.Clear();
  std::vector<uint64_t> ids = {42};
  uint64_t key = UdfCacheKey(9, 3, "udf:scale:2", 1, ids.data(), 1);
  CHECK_TRUE(c.Get(key, 9, 3, "udf:scale:2", 1, ids.data(), 1) == nullptr);
  auto col = std::make_shared<CachedColumn>();
  col->graph_uid = 9;
  col->generation = 3;
  col->spec = "udf:scale:2";
  col->fid = 1;
  col->ids = ids;
  col->offs = {0, 2};
  col->vals = {1.f, 2.f};
  c.Put(key, col);
  auto hit = c.Get(key, 9, 3, "udf:scale:2", 1, ids.data(), 1);
  CHECK_TRUE(hit != nullptr && hit->vals.size() == 2);
  // same bucket, different full key (simulated collision) → miss
  CHECK_TRUE(c.Get(key, 9, 4, "udf:scale:2", 1, ids.data(), 1) == nullptr);
  uint64_t h, m, e, b;
  c.Stats(&h, &m, &e, &b);
  CHECK_TRUE(e >= 1 && b > 0);
  // restore the production default: the cache is a process singleton
  // and later tests must not inherit this test's tiny capacity
  c.SetCapacityBytes(64u << 20);
  c.Clear();
}

// ---- serde ----
void TestTensorSerde() {
  Tensor t(DType::kF32, {2, 3});
  for (int i = 0; i < 6; ++i) t.Flat<float>()[i] = i * 1.5f;
  ByteWriter w;
  EncodeTensor(t, &w);
  ByteReader r(w.buffer().data(), w.buffer().size());
  Tensor back;
  CHECK_OK(DecodeTensor(&r, &back));
  CHECK_TRUE(back.dims() == t.dims());
  CHECK_TRUE(std::memcmp(back.raw(), t.raw(), t.ByteSize()) == 0);

  // corrupt header must be rejected, not crash
  std::vector<char> evil(w.buffer());
  int64_t huge = 1LL << 50;
  std::memcpy(evil.data() + 8, &huge, 8);
  ByteReader r2(evil.data(), evil.size());
  Tensor bad;
  CHECK_TRUE(!DecodeTensor(&r2, &bad).ok());
}

// ---- executor ----
void TestExecutorRunsDag() {
  // the fusion assertions below require FuseLocalPass active; restore
  // the caller's knob afterwards so a NO_FUSE suite run stays NO_FUSE
  const char* saved_ptr = getenv("EULER_TPU_NO_FUSE");
  // copy before unsetenv: POSIX allows unsetenv to invalidate the pointer
  std::string saved_no_fuse = saved_ptr != nullptr ? saved_ptr : "";
  bool had_no_fuse = saved_ptr != nullptr;
  unsetenv("EULER_TPU_NO_FUSE");
  struct RestoreEnv {
    std::string saved;
    bool had;
    ~RestoreEnv() {
      if (had) setenv("EULER_TPU_NO_FUSE", saved.c_str(), 1);
    }
  } restore{saved_no_fuse, had_no_fuse};
  // AS chain through the executor against a real graph
  auto g = RingGraph();
  CompileOptions opts;
  opts.mode = "local";
  GqlCompiler compiler(opts);
  std::shared_ptr<const TranslateResult> plan;
  CHECK_OK(compiler.Compile("v(roots).getNB(*).as(nb)", &plan));
  OpKernelContext ctx;
  Tensor roots(DType::kU64, {2});
  roots.Flat<uint64_t>()[0] = 3;
  roots.Flat<uint64_t>()[1] = 9;
  ctx.Put("roots", std::move(roots));
  QueryEnv env;
  env.graph = g.get();
  Executor exec(&plan->dag, env, &ctx);
  CHECK_OK(exec.RunSync());
  Tensor out;
  CHECK_TRUE(ctx.Get("nb:1", &out));
  CHECK_TRUE(out.NumElements() == 2);
  CHECK_TRUE(out.Flat<uint64_t>()[0] == 4);
  CHECK_TRUE(out.Flat<uint64_t>()[1] == 10);

  // local mode fuses the whole plan into one FUSED node (FuseLocalPass);
  // assert that so the sanitizer runs exercise FusedOp intentionally
  CHECK_TRUE(plan->dag.nodes.size() == 1);
  CHECK_TRUE(plan->dag.nodes[0].op == "FUSED");
  CHECK_TRUE(plan->dag.nodes[0].inner.size() >= 2);

  // a multi-hop sampling chain through the fused path
  std::shared_ptr<const TranslateResult> plan2;
  CHECK_OK(compiler.Compile(
      "v(roots).sampleNB(*, 3, 0).as(h0).sampleNB(*, 2, 0).as(h1)", &plan2));
  OpKernelContext ctx2;
  Tensor roots2(DType::kU64, {2});
  roots2.Flat<uint64_t>()[0] = 1;
  roots2.Flat<uint64_t>()[1] = 5;
  ctx2.Put("roots", std::move(roots2));
  Executor exec2(&plan2->dag, env, &ctx2);
  CHECK_OK(exec2.RunSync());
  Tensor h1;
  CHECK_TRUE(ctx2.Get("h1:1", &h1));
  CHECK_TRUE(h1.NumElements() == 2 * 3 * 2);
}

// ---- index ----
void TestIndexDnf() {
  auto g = RingGraph();
  IndexManager idx;
  CHECK_OK(idx.BuildFromSpec(*g, "f:range_index"));
  IndexResult res;
  CHECK_OK(idx.EvalDnf(g.get(), {{"f gt 8"}}, &res));
  CHECK_TRUE(res.rows.size() == 2);  // f = 9, 10
  // id membership keeps (row, weight) pairing even out of order
  IndexResult r2;
  CHECK_OK(idx.EvalDnf(g.get(), {{"id in 9:2"}}, &r2));
  CHECK_TRUE(r2.rows.size() == 2);
  std::map<uint32_t, float> got;
  for (size_t i = 0; i < r2.rows.size(); ++i) got[r2.rows[i]] = r2.weights[i];
  CHECK_TRUE(got[g->NodeIndex(9)] == 9.0f);
  CHECK_TRUE(got[g->NodeIndex(2)] == 2.0f);
}

// ---- dump/load ----
void TestDumpLoadRoundtrip() {
  auto g = RingGraph();
  std::string dir = "/tmp/et_engine_test_dump";
  std::string cmd = "mkdir -p " + dir;
  CHECK_TRUE(std::system(cmd.c_str()) == 0);
  CHECK_OK(DumpGraphPartitioned(*g, dir, 2));
  std::unique_ptr<Graph> back;
  CHECK_OK(LoadShard(dir, 0, 1, 0, true, &back));
  CHECK_TRUE(back->node_count() == 10);
  CHECK_TRUE(back->edge_count() == 10);
}

// ---- out-of-core columnar store ----
// One hub (node 1, degree 63) plus a sparse tail, two node/edge types,
// every feature kind — exercises each column family the store
// serializes and gives the hub-first hot-set chooser a clear winner.
std::unique_ptr<Graph> OutcoreGraph() {
  GraphBuilder b;
  for (uint64_t i = 1; i <= 64; ++i)
    b.AddNode(i, static_cast<int32_t>(i % 2), static_cast<float>(i));
  for (uint64_t i = 2; i <= 64; ++i)
    b.AddEdge(1, i, 0, static_cast<float>(i));
  for (uint64_t i = 2; i <= 64; ++i) b.AddEdge(i, i % 64 + 1, 1, 1.0f);
  b.mutable_meta()->node_features.push_back({"d", FeatureKind::kDense, 4});
  b.mutable_meta()->node_features.push_back({"s", FeatureKind::kSparse, 0});
  b.mutable_meta()->node_features.push_back({"b", FeatureKind::kBinary, 0});
  b.mutable_meta()->edge_features.push_back({"ed", FeatureKind::kDense, 2});
  for (uint64_t i = 1; i <= 64; ++i) {
    float v[4];
    for (int k = 0; k < 4; ++k) v[k] = static_cast<float>(i * 10 + k);
    b.SetNodeDense(i, 0, v, 4);
    uint64_t sp[2] = {i, i * 7};
    b.SetNodeSparse(i, 1, sp, 2);
    std::string bytes = "blob_" + std::to_string(i);
    b.SetNodeBinary(i, 2, bytes.data(), static_cast<int64_t>(bytes.size()));
  }
  for (uint64_t i = 2; i <= 64; ++i) {
    float ev[2] = {static_cast<float>(i), static_cast<float>(-2.0 * i)};
    b.SetEdgeDense(1, i, 0, 0, ev, 2);
  }
  return b.Finalize();
}

// Full-read parity between two graphs: adjacency (both directions),
// every feature kind, and seeded sampler draws. The store's contract is
// byte-identity with its heap twin, so equality here is exact.
void CheckGraphParity(const Graph& a, const Graph& b) {
  CHECK_TRUE(a.node_count() == b.node_count());
  CHECK_TRUE(a.edge_count() == b.edge_count());
  CHECK_TRUE(a.epoch() == b.epoch());
  for (uint64_t id = 1; id <= a.node_count() + 1; ++id) {
    std::vector<NodeId> ia, ib;
    std::vector<float> wa, wb;
    std::vector<int32_t> ta, tb;
    a.GetFullNeighbor(id, nullptr, 0, &ia, &wa, &ta);
    b.GetFullNeighbor(id, nullptr, 0, &ib, &wb, &tb);
    CHECK_TRUE(ia == ib && wa == wb && ta == tb);
    ia.clear(); ib.clear(); wa.clear(); wb.clear(); ta.clear(); tb.clear();
    a.GetFullInNeighbor(id, nullptr, 0, &ia, &wa, &ta);
    b.GetFullInNeighbor(id, nullptr, 0, &ib, &wb, &tb);
    CHECK_TRUE(ia == ib && wa == wb && ta == tb);
    NodeId nid = id;
    float da[4] = {0}, db[4] = {0};
    a.GetDenseFeature(&nid, 1, 0, 4, da);
    b.GetDenseFeature(&nid, 1, 0, 4, db);
    CHECK_TRUE(std::memcmp(da, db, sizeof(da)) == 0);
    std::vector<uint64_t> oa, ob, va, vb;
    a.GetSparseFeature(&nid, 1, 1, &oa, &va);
    b.GetSparseFeature(&nid, 1, 1, &ob, &vb);
    CHECK_TRUE(oa == ob && va == vb);
    std::vector<uint64_t> boa, bob;
    std::vector<char> bva, bvb;
    a.GetBinaryFeature(&nid, 1, 2, &boa, &bva);
    b.GetBinaryFeature(&nid, 1, 2, &bob, &bvb);
    CHECK_TRUE(boa == bob && bva == bvb);
  }
  {
    NodeId s = 1, d = 5;
    int32_t t = 0;
    float ea[2] = {0}, eb[2] = {0};
    a.GetEdgeDenseFeature(&s, &d, &t, 1, 0, 2, ea);
    b.GetEdgeDenseFeature(&s, &d, &t, 1, 0, 2, eb);
    CHECK_TRUE(std::memcmp(ea, eb, sizeof(ea)) == 0);
  }
  // Seeded draws must match stream-for-stream: the alias tables and the
  // row order serialized verbatim (never hub-sorted).
  Pcg32 ra(99), rb(99);
  NodeId sa[16], sb[16];
  a.SampleNode(-1, 16, &ra, sa);
  b.SampleNode(-1, 16, &rb, sb);
  CHECK_TRUE(std::memcmp(sa, sb, sizeof(sa)) == 0);
  float wsa[8], wsb[8];
  int32_t tsa[8], tsb[8];
  a.SampleNeighbor(1, nullptr, 0, 8, 0, &ra, sa, wsa, tsa);
  b.SampleNeighbor(1, nullptr, 0, 8, 0, &rb, sb, wsb, tsb);
  CHECK_TRUE(std::memcmp(sa, sb, 8 * sizeof(NodeId)) == 0);
  CHECK_TRUE(std::memcmp(wsa, wsb, sizeof(wsa)) == 0);
}

void TestColumnarStoreRoundtrip() {
  auto g = OutcoreGraph();
  CHECK_TRUE(std::system("mkdir -p /tmp/et_engine_test_store") == 0);
  std::string path = "/tmp/et_engine_test_store/columnar.etc";
  CHECK_OK(WriteColumnarStore(*g, path));

  auto& c = GlobalStoreCounters();
  uint64_t hits0 = c.hot_hits.load(), cold0 = c.cold_reads.load();
  // All-hot attach: every read classifies hot, none cold.
  std::unique_ptr<Graph> hot;
  CHECK_OK(LoadGraphFromStore(path, 1LL << 30, &hot));
  CHECK_TRUE(hot->attached());
  CHECK_TRUE(hot->tier() != nullptr);
  CHECK_TRUE(hot->tier()->hot_rows() == hot->node_count());
  CheckGraphParity(*g, *hot);
  CHECK_TRUE(c.hot_hits.load() > hits0);
  CHECK_TRUE(c.cold_reads.load() == cold0);

  // Zero-budget attach: parity still exact, reads classify cold and the
  // cold-read histogram moves.
  uint64_t hist_n0 = c.cold_hist.n.load();
  std::unique_ptr<Graph> cold;
  CHECK_OK(LoadGraphFromStore(path, 0, &cold));
  CHECK_TRUE(cold->tier()->hot_rows() == 0);
  CheckGraphParity(*g, *cold);
  CHECK_TRUE(c.cold_reads.load() > cold0);
  CHECK_TRUE(c.cold_hist.n.load() > hist_n0);

  // The stats snapshot surfaces the mapping gauges.
  uint64_t st[kStoreStatSlots];
  StoreStatsSnapshot(st);
  CHECK_TRUE(st[5] > 0);   // mapped_bytes
  CHECK_TRUE(st[7] >= 2);  // attaches
}

// The RAM overlay above the mmap base: applying the same delta to the
// heap twin and the attached graph must yield byte-identical snapshots
// (ISSUE gate: post-delta reads byte-identical to the RAM engine).
void TestColumnarStorePostDelta() {
  auto base = OutcoreGraph();
  CHECK_TRUE(std::system("mkdir -p /tmp/et_engine_test_store") == 0);
  std::string path = "/tmp/et_engine_test_store/delta.etc";
  CHECK_OK(WriteColumnarStore(*base, path));
  std::unique_ptr<Graph> mm;
  CHECK_OK(LoadGraphFromStore(path, 1 << 20, &mm));

  // update node 5's weight, add node 100, re-weight hub edge (1,2,0),
  // add a fresh edge (3,7,1)
  NodeId nids[2] = {5, 100};
  int32_t ntypes[2] = {1, 0};
  float nws[2] = {50.0f, 1.0f};
  NodeId esrc[2] = {1, 3}, edst[2] = {2, 7};
  int32_t etypes[2] = {0, 1};
  float ews[2] = {9.0f, 2.5f};
  std::unique_ptr<Graph> next_heap, next_mm;
  std::vector<NodeId> dirty_h, dirty_m;
  CHECK_OK(ApplyGraphDelta(*base, nids, ntypes, nws, 2, esrc, edst, etypes,
                           ews, 2, 0, 1, &next_heap, &dirty_h));
  CHECK_OK(ApplyGraphDelta(*mm, nids, ntypes, nws, 2, esrc, edst, etypes,
                           ews, 2, 0, 1, &next_mm, &dirty_m));
  CHECK_TRUE(dirty_h == dirty_m);
  CheckGraphParity(*next_heap, *next_mm);
  // the delta snapshot itself is a heap overlay until the next spill
  CHECK_TRUE(!next_mm->attached());
}

// WAL compaction emits the columnar sidecar; recovery with storage=mmap
// attaches it and replays the tail to the same graph the heap path
// rebuilds.
void TestWalColumnarSidecarRecovery() {
  std::string root = "/tmp/et_engine_test_walcol";
  CHECK_TRUE(std::system(("rm -rf " + root + " && mkdir -p " + root +
                          "/data " + root + "/wal").c_str()) == 0);
  auto g = OutcoreGraph();
  CHECK_OK(DumpGraphPartitioned(*g, root + "/data", 1));

  std::unique_ptr<DeltaWal> wal;
  CHECK_OK(DeltaWal::Open(root + "/wal", FsyncPolicy::kNever, 1, &wal));
  wal->set_columnar_sidecar(true);
  // one delta record (kApplyDelta wire body), epoch 0 -> 1
  ByteWriter body;
  NodeId nid = 200;
  int32_t ntype = 1;
  float nw = 3.0f;
  NodeId esrc = 200, edst = 1;
  int32_t etype = 0;
  float ew = 4.0f;
  body.Put<uint64_t>(1);
  body.PutRaw(&nid, sizeof(nid));
  body.PutRaw(&ntype, sizeof(ntype));
  body.PutRaw(&nw, sizeof(nw));
  body.Put<uint64_t>(1);
  body.PutRaw(&esrc, sizeof(esrc));
  body.PutRaw(&edst, sizeof(edst));
  body.PutRaw(&etype, sizeof(etype));
  body.PutRaw(&ew, sizeof(ew));
  CHECK_OK(wal->Append(1, body.buffer().data(), body.buffer().size()));

  // heap-path recovery replays the record…
  std::unique_ptr<Graph> heap_g;
  uint64_t replayed = 0;
  CHECK_OK(RecoverShard(root + "/wal", root + "/data", 0, 1, true, &heap_g,
                        &replayed));
  CHECK_TRUE(replayed == 1);
  CHECK_TRUE(heap_g->epoch() == 1);

  // …compaction snapshots it WITH the sidecar…
  CHECK_OK(wal->Compact(*heap_g));
  CHECK_TRUE(!wal->last_snapshot_dir().empty());
  std::string sidecar = wal->last_snapshot_dir() + "/" + kColumnarFileName;
  std::unique_ptr<Graph> side_g;
  CHECK_OK(LoadGraphFromStore(sidecar, 0, &side_g));
  CheckGraphParity(*heap_g, *side_g);

  // …and a fresh mmap-mode recovery attaches it (no pending tail).
  std::unique_ptr<Graph> mm_g;
  CHECK_OK(RecoverShard(root + "/wal", root + "/data", 0, 1, true, &mm_g,
                        nullptr, nullptr, nullptr, nullptr, 1, 1 << 20));
  CHECK_TRUE(mm_g->attached());
  CheckGraphParity(*heap_g, *mm_g);
}

// Hardening (review r18): shard-qualified sidecar names, freshness
// gating against re-dumped partition files, overflow-safe header
// bounds, typed-column size verification, and residency-gauge walks
// racing tier teardown.
void TestColumnarStoreHardening() {
  CHECK_TRUE(ColumnarSidecarName(0, 1) == std::string(kColumnarFileName));
  CHECK_TRUE(ColumnarSidecarName(2, 4) == "columnar.2of4.etc");

  std::string root = "/tmp/et_engine_test_fresh";
  CHECK_TRUE(
      std::system(("rm -rf " + root + " && mkdir -p " + root).c_str()) == 0);
  auto g = OutcoreGraph();
  CHECK_OK(DumpGraphPartitioned(*g, root, 1));
  std::string sidecar = root + "/" + kColumnarFileName;
  CHECK_TRUE(!SidecarIsFresh(root, sidecar));  // nothing spilled yet
  CHECK_OK(WriteColumnarStore(*g, sidecar));
  CHECK_TRUE(SidecarIsFresh(root, sidecar));  // spill postdates the parts
  // simulate an in-place re-dump (partition files newer than the
  // spill) by backdating the sidecar — deterministic even on coarse
  // mtime clocks, where touching a part file "now" can tie the spill
  struct timespec back[2];
  back[0].tv_sec = 0;
  back[0].tv_nsec = UTIME_OMIT;
  back[1].tv_sec = 1;  // epoch+1s: long before the partition files
  back[1].tv_nsec = 0;
  CHECK_TRUE(utimensat(AT_FDCWD, sidecar.c_str(), back, 0) == 0);
  CHECK_TRUE(!SidecarIsFresh(root, sidecar));
  // a sibling shard's spill is NOT a source file: it must not re-stale
  // this shard's fresh sidecar
  CHECK_OK(WriteColumnarStore(*g, sidecar));  // re-spill -> fresh again
  CHECK_TRUE(SidecarIsFresh(root, sidecar));
  CHECK_OK(WriteColumnarStore(*g, root + "/" + ColumnarSidecarName(1, 2)));
  CHECK_TRUE(SidecarIsFresh(root, sidecar));

  // typed Find rejects a size-mismatched column instead of
  // reinterpreting it (reads past the mapping otherwise)
  std::shared_ptr<ColumnarStore> store;
  CHECK_OK(ColumnarStore::Open(sidecar, &store));
  const uint64_t* p64 = nullptr;
  const float* p32 = nullptr;
  size_t n = 0;
  CHECK_TRUE(store->Find("node_ids", &p64, &n) && n > 0);  // u64: matches
  CHECK_TRUE(!store->Find("node_ids", &p32, &n));          // f32: rejected

  // corrupt header: a count whose byte size wraps uint64 must be
  // rejected, not accepted by an overflowed bounds check. The first
  // column entry ("aux", elem_size 1) puts count at byte 31.
  {
    std::FILE* f = std::fopen(sidecar.c_str(), "rb");
    CHECK_TRUE(f != nullptr);
    std::fseek(f, 0, SEEK_END);
    std::vector<char> bytes(std::ftell(f));
    std::fseek(f, 0, SEEK_SET);
    CHECK_TRUE(std::fread(bytes.data(), 1, bytes.size(), f) == bytes.size());
    std::fclose(f);
    uint64_t huge = ~0ULL;
    std::memcpy(bytes.data() + 31, &huge, sizeof(huge));
    std::string bad = root + "/bad.etc";
    f = std::fopen(bad.c_str(), "wb");
    CHECK_TRUE(f != nullptr &&
               std::fwrite(bytes.data(), 1, bytes.size(), f) == bytes.size());
    std::fclose(f);
    std::shared_ptr<ColumnarStore> rejected;
    CHECK_TRUE(!ColumnarStore::Open(bad, &rejected).ok());
  }

  // residency gauges vs. tier teardown: StoreStatsSnapshot walks the
  // tier registry while attach/destroy churns it (the reattach swap) —
  // the sanitizer targets fail here if the walk reads a dead tier
  std::atomic<bool> stop{false};
  std::thread scraper([&] {
    uint64_t st[kStoreStatSlots];
    while (!stop.load()) StoreStatsSnapshot(st);
  });
  for (int i = 0; i < 50; ++i) {
    std::unique_ptr<Graph> att;
    CHECK_OK(LoadGraphFromStore(sidecar, 1 << 16, &att));
  }
  stop.store(true);
  scraper.join();
}

// Ragged offsets travel as i32 [n,2]; every merge producer range-checks
// its final cursor (advisor r1: >2^31-element merges would silently
// wrap). Exercise the guard on both sides of the boundary — allocating
// a real >2GB payload in a unit test is not viable, and every producer
// funnels through this one check.
void TestI32OffsetGuard() {
  NodeDef node;
  node.name = "GP_RAGGED_MERGE_test";
  CHECK_OK(CheckI32Offsets(node, 0));
  CHECK_OK(CheckI32Offsets(node, (1LL << 31) - 1));
  Status s = CheckI32Offsets(node, 1LL << 31);
  CHECK_TRUE(!s.ok());
  CHECK_TRUE(s.message().find("int32 offset range") != std::string::npos);
  CHECK_TRUE(s.message().find(node.name) != std::string::npos);
  CHECK_TRUE(!CheckI32Offsets(node, (1LL << 40)).ok());
}


// TCP registry server: concurrent put/list/remove through the real
// socket path (ZK-role discovery without a shared FS) — TSAN covers the
// entries_/conns_ locking and the reap-on-accept path.
void TestRegistryServer() {
  RegistryServer reg;
  CHECK_OK(reg.Start(0));
  std::string spec = "tcp:127.0.0.1:" + std::to_string(reg.port());
  // concurrent heartbeats from several "shards"
  ThreadPool pool(4);
  std::atomic<int> remaining{12};
  std::mutex mu;
  std::condition_variable cv;
  for (int i = 0; i < 12; ++i) {
    pool.Schedule([&, i] {
      std::string name = "shard_" + std::to_string(i % 3) +
                         "__127.0.0.1_" + std::to_string(9000 + i % 3);
      CHECK_OK(RegistryPutEntry(spec, name));
      if (remaining.fetch_sub(1) == 1) {
        std::lock_guard<std::mutex> lk(mu);
        cv.notify_one();
      }
    });
  }
  {
    std::unique_lock<std::mutex> lk(mu);
    cv.wait(lk, [&] { return remaining.load() == 0; });
  }
  std::map<int, std::pair<std::string, int>> found;
  std::map<int, int64_t> ages;
  CHECK_OK(ScanRegistrySpec(spec, &found, &ages));
  CHECK_TRUE(found.size() == 3);
  CHECK_TRUE(found[1].second == 9001);
  CHECK_TRUE(ages[0] >= 0 && ages[0] < 60000);
  // youngest-entry-wins: a NEW registration for shard 0 supersedes
  CHECK_OK(RegistryPutEntry(spec, "shard_0__127.0.0.1_9100"));
  found.clear();
  ages.clear();
  CHECK_OK(ScanRegistrySpec(spec, &found, &ages));
  CHECK_TRUE(found[0].second == 9100);
  // remove drops the entry
  CHECK_OK(RegistryRemoveEntry(spec, "shard_2__127.0.0.1_9002"));
  found.clear();
  CHECK_OK(ScanRegistrySpec(spec, &found, nullptr));
  CHECK_TRUE(found.find(2) == found.end());
  reg.Stop();
  // a scan against the stopped server fails cleanly (bounded)
  found.clear();
  CHECK_TRUE(!ScanRegistrySpec(spec, &found, nullptr).ok());
}

// ---- rpc: protocol v2 mux transport ----
void TestRpcMuxTransport() {
  std::shared_ptr<const Graph> g(RingGraph());
  // heap-held: a stack-placed server's mutexes land on addresses a
  // prior test's destroyed locals used, which TSAN misreads
  auto server = std::make_unique<GraphServer>(g, nullptr, 0, 1, 1);
  CHECK_OK(server->Start(0));

  RpcConfig saved = GlobalRpcConfig();
  GlobalRpcConfig().mux = true;
  GlobalRpcConfig().mux_connections = 1;
  GlobalRpcConfig().compress_threshold = 64;
  auto& ctr = GlobalRpcCounters();

  // v1 reference bytes (classic channel, no mux)
  RpcChannel v1ch("127.0.0.1", server->port());
  std::vector<char> v1_meta;
  CHECK_OK(v1ch.Call(1 /*kMeta*/, {}, &v1_meta));
  CHECK_TRUE(!v1_meta.empty());

  // many concurrent in-flight calls over ONE mux connection; replies
  // come back out-of-order and must route to the right caller
  uint64_t conns0 = ctr.connections_opened.load();
  RpcChannel ch("127.0.0.1", server->port());
  ch.set_mux(true);
  {
    ThreadPool pool(8);
    std::atomic<int> remaining{32};
    std::atomic<bool> all_ok{true};
    std::mutex mu;
    std::condition_variable cv;
    for (int i = 0; i < 32; ++i) {
      pool.Schedule([&, i] {
        std::vector<char> reply;
        uint32_t mt = (i % 2 == 0) ? 1u /*kMeta*/ : 2u /*kPing*/;
        Status s = ch.Call(mt, {}, &reply);
        if (!s.ok() || (mt == 1 && reply != v1_meta)) all_ok.store(false);
        if (remaining.fetch_sub(1) == 1) {
          std::lock_guard<std::mutex> lk(mu);
          cv.notify_one();
        }
      });
    }
    std::unique_lock<std::mutex> lk(mu);
    cv.wait(lk, [&] { return remaining.load() == 0; });
    CHECK_TRUE(all_ok.load());
  }
  CHECK_TRUE(ch.mux_active());
  // 32 calls rode exactly one new connection
  CHECK_TRUE(ctr.connections_opened.load() - conns0 == 1);

  // async surface: reply delivered via callback on the client pool
  {
    std::mutex mu;
    std::condition_variable cv;
    bool fired = false;
    Status got = Status::IOError("not fired");
    ch.CallAsync(2 /*kPing*/, {}, [&](Status s, std::vector<char>) {
      std::lock_guard<std::mutex> lk(mu);
      got = s;
      fired = true;
      cv.notify_one();
    });
    std::unique_lock<std::mutex> lk(mu);
    cv.wait(lk, [&] { return fired; });
    CHECK_OK(got);
  }

  // kill the server while callers hammer the channel: every parked
  // waiter must come back with a STATUS (the joins below are the
  // no-hang assertion)
  {
    std::atomic<bool> saw_failure{false};
    std::vector<std::thread> threads;
    for (int t = 0; t < 4; ++t) {
      threads.emplace_back([&] {
        for (int i = 0; i < 200; ++i) {
          std::vector<char> reply;
          if (!ch.Call(2 /*kPing*/, {}, &reply, /*max_retries=*/2).ok()) {
            saw_failure.store(true);
            return;
          }
        }
      });
    }
    ::usleep(5000);
    server->Stop();
    for (auto& th : threads) th.join();
    CHECK_TRUE(saw_failure.load());
  }
  GlobalRpcConfig() = saved;
}

// ---- rpc: v2 client against a v1-only server falls back cleanly ----
void TestRpcHelloFallback() {
  std::shared_ptr<const Graph> g(RingGraph());
  ::setenv("EULER_TPU_RPC_SERVER_V1", "1", 1);
  auto server = std::make_unique<GraphServer>(g, nullptr, 0, 1, 1);
  CHECK_OK(server->Start(0));
  ::unsetenv("EULER_TPU_RPC_SERVER_V1");

  RpcConfig saved = GlobalRpcConfig();
  GlobalRpcConfig().mux = true;
  auto& ctr = GlobalRpcCounters();
  uint64_t fb0 = ctr.hello_fallbacks.load();

  RpcChannel v1ch("127.0.0.1", server->port());
  std::vector<char> v1_meta;
  CHECK_OK(v1ch.Call(1 /*kMeta*/, {}, &v1_meta));

  RpcChannel ch("127.0.0.1", server->port());
  ch.set_mux(true);
  std::vector<char> meta;
  CHECK_OK(ch.Call(1 /*kMeta*/, {}, &meta));  // hello refused → v1 path
  CHECK_TRUE(meta == v1_meta);
  CHECK_TRUE(!ch.mux_active());
  CHECK_TRUE(ctr.hello_fallbacks.load() == fb0 + 1);
  server->Stop();
  GlobalRpcConfig() = saved;
}

// ---- rpc: wire trace context → server-side timing breakdown ----
void TestServerTraceBreakdown() {
  std::shared_ptr<const Graph> g(RingGraph());
  auto server = std::make_unique<GraphServer>(g, nullptr, 0, 1, 1);
  CHECK_OK(server->Start(0));
  RpcConfig saved = GlobalRpcConfig();
  GlobalRpcConfig().mux = true;
  GlobalRpcConfig().mux_connections = 1;
  auto& ctr = GlobalRpcCounters();

  // drain whatever earlier tests' traffic left in the ring
  std::vector<ServerTraceRecord> recs;
  GlobalServerTraceStats().Drain(&recs);

  ExecuteRequest req;  // empty DAG: decode/execute/serialize still run
  ByteWriter w;
  EncodeExecuteRequest(req, &w);

  RpcChannel ch("127.0.0.1", server->port());
  ch.set_mux(true);
  std::vector<char> reply;
  uint64_t t0 = ctr.trace_propagated.load();
  // the server books a call (phase histograms, serialize last, then the
  // ring) AFTER it has written the reply, so the caller can be back
  // first: wait until `calls` more of them are booked
  uint64_t n = 0, sum = 0;
  uint64_t counts[ServerTraceStats::kTraceBuckets + 1];
  GlobalServerTraceStats().HistSnapshot(0, 3, &n, &sum, counts);
  const uint64_t booked0 = n;
  auto wait_booked = [&](uint64_t calls) {
    for (int i = 0; i < 5000; ++i) {
      GlobalServerTraceStats().HistSnapshot(0, 3, &n, &sum, counts);
      if (n >= booked0 + calls) return;
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
    }
  };

  // untraced call: nothing stamped, nothing ringed (wire identity is
  // pinned at the byte level by the Python interop tests)
  CHECK_OK(ch.Call(0 /*kExecute*/, w.buffer(), &reply, /*max_retries=*/2));
  CHECK_TRUE(ctr.trace_propagated.load() == t0);
  wait_booked(1);
  GlobalServerTraceStats().Drain(&recs);
  CHECK_TRUE(recs.empty());

  // traced call: stamped, and the server records the breakdown under
  // the caller's trace/parent with a freshly minted span id
  CHECK_OK(ch.Call(0, w.buffer(), &reply, 2, /*deadline=*/0,
                   /*map_epoch=*/0, WireTrace{77, 5}));
  CHECK_TRUE(ctr.trace_propagated.load() == t0 + 1);
  wait_booked(2);
  for (int i = 0; i < 5000 && recs.empty(); ++i) {
    GlobalServerTraceStats().Drain(&recs);
    if (recs.empty())
      std::this_thread::sleep_for(std::chrono::milliseconds(1));
  }
  CHECK_TRUE(recs.size() == 1);
  CHECK_TRUE(recs[0].trace_id == 77 && recs[0].parent_span == 5);
  CHECK_TRUE(recs[0].span_id != 0);
  CHECK_TRUE(recs[0].verb == 0 && recs[0].flags == 0);
  CHECK_TRUE(recs[0].start_unix_us > 0);

  // the always-on phase histograms saw both calls (queue + execute)
  CHECK_TRUE(GlobalServerTraceStats().HistSnapshot(0, 0, &n, &sum, counts));
  CHECK_TRUE(n >= 2);
  CHECK_TRUE(GlobalServerTraceStats().HistSnapshot(0, 2, &n, &sum, counts));
  CHECK_TRUE(n >= 2);

  server->Stop();
  GlobalRpcConfig() = saved;
}

// ---- serde: sizing-reserved encodes + split-plan + reply segments ----
void TestSerdeSizingSplitSegments() {
  // request with a payload-bearing feed and a small multi-node plan
  ExecuteRequest req;
  Tensor roots(DType::kU64, {4});
  for (int i = 0; i < 4; ++i) roots.Flat<uint64_t>()[i] = 100 + i;
  req.inputs.emplace_back("roots", roots);
  NodeDef nd;
  nd.name = "SAMPLE_NB_0";
  nd.op = "SAMPLE_NB";
  nd.inputs = {"roots"};
  nd.attrs = {"*", "3", "0"};
  req.nodes.push_back(nd);
  req.outputs = {"SAMPLE_NB_0:0", "SAMPLE_NB_0:1"};

  // the documented invariant: 'ETEY' + feeds[4:] + plan[4:] is byte-
  // identical to the classic full encoding (the fallback reassembly)
  ByteWriter full, pw, fw;
  EncodeExecuteRequest(req, &full);
  EncodeExecutePlan(req, &pw);
  EncodeExecuteFeeds(req, &fw);
  std::vector<char> assembled;
  CHECK_OK(AssembleFullExecuteRequest(fw.buffer(), pw.buffer(), &assembled));
  CHECK_TRUE(assembled == full.buffer());
  // swapped arguments must fail fast, not misread
  CHECK_TRUE(
      !AssembleFullExecuteRequest(pw.buffer(), fw.buffer(), &assembled)
           .ok());

  // split halves decode back to the original request
  ExecuteRequest back;
  {
    ByteReader r(pw.buffer().data(), pw.buffer().size());
    CHECK_OK(DecodeExecutePlan(&r, &back));
    CHECK_TRUE(r.remaining() == 0);
    ByteReader r2(fw.buffer().data(), fw.buffer().size());
    CHECK_OK(DecodeExecuteFeeds(&r2, &back));
    CHECK_TRUE(r2.remaining() == 0);
  }
  CHECK_TRUE(back.nodes.size() == 1 && back.nodes[0].op == "SAMPLE_NB");
  CHECK_TRUE(back.outputs == req.outputs);
  CHECK_TRUE(back.inputs.size() == 1 &&
             std::memcmp(back.inputs[0].second.raw(), roots.raw(),
                         roots.ByteSize()) == 0);

  // content hash: stable, non-zero, and sensitive to any plan byte
  uint64_t h1 = PlanContentHash(pw.buffer().data(), pw.buffer().size());
  uint64_t h2 = PlanContentHash(pw.buffer().data(), pw.buffer().size());
  CHECK_TRUE(h1 == h2 && h1 != 0);
  std::vector<char> tweaked(pw.buffer());
  tweaked.back() ^= 1;
  CHECK_TRUE(PlanContentHash(tweaked.data(), tweaked.size()) != h1);

  // reply segments: runs concatenated in order == EncodeExecuteReply
  ExecuteReply rep;
  rep.status = Status::OK();
  Tensor t1(DType::kF32, {3, 5});
  for (int i = 0; i < 15; ++i) t1.Flat<float>()[i] = i * 0.5f;
  Tensor t2(DType::kU64, {0});  // empty payload: meta-only run
  Tensor t3(DType::kI32, {7});
  for (int i = 0; i < 7; ++i) t3.Flat<int32_t>()[i] = -i;
  rep.outputs.emplace_back("a:0", t1);
  rep.outputs.emplace_back("b:0", t2);
  rep.outputs.emplace_back("c:0", t3);
  ByteWriter contiguous;
  EncodeExecuteReply(rep, &contiguous);
  ReplySegments segs;
  EncodeExecuteReplySegments(std::move(rep), &segs);
  std::vector<char> glued;
  for (const auto& run : segs.runs) {
    const char* p = run.tensor >= 0
                        ? reinterpret_cast<const char*>(
                              segs.tensors[run.tensor].raw())
                        : segs.meta.buffer().data() + run.off;
    glued.insert(glued.end(), p, p + run.len);
  }
  CHECK_TRUE(glued == contiguous.buffer());
  CHECK_TRUE(segs.total == contiguous.buffer().size());
  // tensor payloads are VIEWS (two payload-bearing tensors pinned)
  CHECK_TRUE(segs.tensors.size() == 2);

  // error replies segment too (no outputs encoded)
  ExecuteReply bad;
  bad.status = Status::Internal("boom");
  ByteWriter bad_c;
  EncodeExecuteReply(bad, &bad_c);
  ReplySegments bad_s;
  EncodeExecuteReplySegments(std::move(bad), &bad_s);
  CHECK_TRUE(bad_s.runs.size() == 1 && bad_s.total == bad_c.buffer().size());
}

// ---- rpc: prepared plans (kPrepare + flagged kExecute) end to end ----
void TestPreparedPlanExecution() {
  std::shared_ptr<const Graph> g(RingGraph());
  auto server = std::make_unique<GraphServer>(g, nullptr, 0, 1, 1);
  CHECK_OK(server->Start(0));
  RpcConfig saved = GlobalRpcConfig();
  GlobalRpcConfig().mux = true;
  GlobalRpcConfig().mux_connections = 1;
  GlobalRpcConfig().prepared = true;
  auto& ctr = GlobalRpcCounters();

  CompileOptions opts;
  opts.mode = "local";
  GqlCompiler compiler(opts);
  std::shared_ptr<const TranslateResult> plan;
  CHECK_OK(compiler.Compile("v(roots).getNB(*).as(nb)", &plan));
  ExecuteRequest req;
  Tensor roots(DType::kU64, {2});
  roots.Flat<uint64_t>()[0] = 3;
  roots.Flat<uint64_t>()[1] = 9;
  req.inputs.emplace_back("roots", roots);
  req.nodes = plan->dag.nodes;
  req.outputs = {"nb:1"};

  ByteWriter full, pw, fw;
  EncodeExecuteRequest(req, &full);
  EncodeExecutePlan(req, &pw);
  EncodeExecuteFeeds(req, &fw);
  const uint64_t pid =
      PlanContentHash(pw.buffer().data(), pw.buffer().size());

  RpcChannel ch("127.0.0.1", server->port());
  ch.set_mux(true);
  // classic full-frame reference reply (same v2 connection family)
  std::vector<char> ref;
  CHECK_OK(ch.Call(0 /*kExecute*/, full.buffer(), &ref, 2));

  // prepared: first call registers once, later calls hit; replies are
  // byte-identical to the classic path (the zero-copy writer included)
  const uint64_t reg0 = ctr.prepared_registered.load();
  const uint64_t hit0 = ctr.prepared_hits.load();
  std::vector<char> rep1, rep2;
  CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rep1, 2));
  CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rep2, 2));
  CHECK_TRUE(rep1 == ref && rep2 == ref);
  CHECK_TRUE(ctr.prepared_registered.load() == reg0 + 1);
  CHECK_TRUE(ctr.prepared_hits.load() == hit0 + 2);

  // a prepared frame ships FEWER bytes than the full frame: the saved
  // wire is the plan bytes minus the 8-byte id prefix
  CHECK_TRUE(fw.buffer().size() + 8 < full.buffer().size());

  // LRU eviction → explicit miss → client re-prepares and converges
  GlobalRpcConfig().plan_cache = 1;
  ExecuteRequest req2 = req;
  req2.outputs = {"nb:0"};  // different plan content → different id
  ByteWriter pw2, fw2;
  EncodeExecutePlan(req2, &pw2);
  EncodeExecuteFeeds(req2, &fw2);
  const uint64_t pid2 =
      PlanContentHash(pw2.buffer().data(), pw2.buffer().size());
  std::vector<char> repB;
  CHECK_OK(
      ch.CallExecutePrepared(pw2.buffer(), pid2, fw2.buffer(), &repB, 2));
  const uint64_t miss0 = ctr.prepared_misses.load();
  std::vector<char> rep3;
  CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rep3, 3));
  CHECK_TRUE(rep3 == ref);
  CHECK_TRUE(ctr.prepared_misses.load() >= miss0 + 1);
  GlobalRpcConfig().plan_cache = 64;

  // ownership-map flip strands every cached plan: the next prepared
  // execute answers the counted invalidation miss, the client
  // re-prepares, and the result is still byte-identical — a stale plan
  // never executes silently
  const uint64_t inv0 = ctr.prepared_invalidated.load();
  auto om = std::make_shared<OwnershipMap>();
  CHECK_OK(OwnershipMap::Decode("e1-P1-0", om.get()));
  CHECK_OK(server->SetOwnership(om));
  std::vector<char> rep4;
  CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rep4, 3));
  CHECK_TRUE(ctr.prepared_invalidated.load() == inv0 + 1);

  // prepared request against a v1-only server: counted fallback, same
  // answer through the classic framing
  ::setenv("EULER_TPU_RPC_SERVER_V1", "1", 1);
  auto v1srv = std::make_unique<GraphServer>(g, nullptr, 0, 1, 1);
  CHECK_OK(v1srv->Start(0));
  ::unsetenv("EULER_TPU_RPC_SERVER_V1");
  RpcChannel chv1("127.0.0.1", v1srv->port());
  chv1.set_mux(true);
  const uint64_t fb0 = ctr.prepared_fallbacks.load();
  std::vector<char> repv1;
  CHECK_OK(
      chv1.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &repv1, 3));
  CHECK_TRUE(ctr.prepared_fallbacks.load() >= fb0 + 1);
  v1srv->Stop();

  server->Stop();
  GlobalRpcConfig() = saved;
}

// ---- gql: prepare-time plan optimizer passes (golden rewrites) ----
void TestPlanOptimizerPasses() {
  // dedup: two identical deterministic gathers; one is a requested
  // output (protected), the duplicate folds into it
  {
    DAGDef dag;
    NodeDef a;
    a.name = "API_GET_P_0";
    a.op = "API_GET_P";
    a.inputs = {"roots"};
    a.attrs = {"price"};
    NodeDef b = a;
    b.name = "API_GET_P_1";
    NodeDef c;
    c.name = "SUM_2";
    c.op = "POST_PROCESS";
    c.inputs = {"API_GET_P_0:0", "API_GET_P_1:0"};
    dag.nodes = {a, b, c};
    dag.next_id = 100;
    PlanOptStats st;
    CHECK_OK(OptimizePreparedPlan(&dag, {"SUM_2:0"}, &st));
    CHECK_TRUE(st.dedup == 1);
    // the duplicate's consumers were rewired onto the survivor
    const NodeDef* kept = dag.Find("SUM_2");
    if (kept == nullptr) {  // whole plan may have fused
      CHECK_TRUE(dag.nodes.size() == 1 && dag.nodes[0].op == "FUSED");
      for (const auto& n : dag.nodes[0].inner)
        if (n.name == "SUM_2") kept = &n;
    }
    CHECK_TRUE(kept != nullptr &&
               kept->inputs == std::vector<std::string>(
                                   {"API_GET_P_0:0", "API_GET_P_0:0"}));
  }
  // filter pushdown: GET_NODE(dnf2) ∘ GET_NODE(dnf1) → one node with
  // dnf1 ∧ dnf2 — but ONLY while the child's :1 positions are unread
  {
    DAGDef dag;
    NodeDef f1;
    f1.name = "API_GET_NODE_0";
    f1.op = "API_GET_NODE";
    f1.inputs = {"roots"};
    f1.dnf = {{"price gt 1"}};
    NodeDef f2;
    f2.name = "API_GET_NODE_1";
    f2.op = "API_GET_NODE";
    f2.inputs = {"API_GET_NODE_0:0"};
    f2.dnf = {{"price lt 9"}};
    dag.nodes = {f1, f2};
    dag.next_id = 100;
    PlanOptStats st;
    CHECK_OK(OptimizePreparedPlan(&dag, {"API_GET_NODE_1:0"}, &st));
    CHECK_TRUE(st.pushdown == 1);
    std::string text = DagToString(dag);
    CHECK_TRUE(text.find("price gt 1 & price lt 9") != std::string::npos);
    // same chain, but the child's :1 (positions) is fetched → no merge
    DAGDef dag2;
    dag2.nodes = {f1, f2};
    dag2.next_id = 100;
    PlanOptStats st2;
    CHECK_OK(OptimizePreparedPlan(
        &dag2, {"API_GET_NODE_1:0", "API_GET_NODE_1:1"}, &st2));
    CHECK_TRUE(st2.pushdown == 0);
  }
  // fusion: a sync multi-node plan collapses into one FUSED group and
  // the executed form stays deterministic
  {
    DAGDef dag;
    NodeDef own;
    own.name = "API_GET_NODE_0";
    own.op = "API_GET_NODE";
    own.inputs = {"roots"};
    NodeDef gp;
    gp.name = "API_GET_P_1";
    gp.op = "API_GET_P";
    gp.inputs = {"API_GET_NODE_0:0"};
    gp.attrs = {"price"};
    dag.nodes = {own, gp};
    dag.next_id = 100;
    PlanOptStats st;
    CHECK_OK(OptimizePreparedPlan(&dag, {"API_GET_P_1:0"}, &st));
    CHECK_TRUE(st.fuse == 2);
    CHECK_TRUE(dag.nodes.size() == 1 && dag.nodes[0].op == "FUSED");
    CHECK_TRUE(DagIsDeterministic(dag));
  }
  // determinism gate: sampling verbs disqualify a plan, FUSED recurses
  {
    DAGDef dag;
    NodeDef s;
    s.name = "API_SAMPLE_NB_0";
    s.op = "API_SAMPLE_NB";
    s.inputs = {"roots"};
    s.attrs = {"*", "3", "0"};
    dag.nodes = {s};
    CHECK_TRUE(!DagIsDeterministic(dag));
    DAGDef fused;
    NodeDef f;
    f.name = "FUSED_1";
    f.op = "FUSED";
    f.inputs = {"roots"};
    f.inner = {s};
    fused.nodes = {f};
    CHECK_TRUE(!DagIsDeterministic(fused));
    CHECK_TRUE(IsDeterministicOp("API_GET_NB_NODE"));
    CHECK_TRUE(!IsDeterministicOp("API_SAMPLE_NB"));
  }
  // compile cache: bounded LRU — a distinct-query flood stays capped
  {
    CompileOptions opts;
    opts.mode = "local";
    GqlCompiler compiler(opts);
    for (int i = 0; i < 300; ++i) {
      std::shared_ptr<const TranslateResult> plan;
      CHECK_OK(compiler.Compile(
          "v(roots).getNB(" + std::to_string(i % 2) + ").as(nb" +
              std::to_string(i) + ")",
          &plan));
    }
    CHECK_TRUE(compiler.cache_size() == GqlCompiler::kCacheCap);
    // an entry still resident answers from cache (same pointer)
    std::shared_ptr<const TranslateResult> p1, p2;
    CHECK_OK(compiler.Compile("v(roots).getNB(0).as(nb299)", &p1));
    CHECK_OK(compiler.Compile("v(roots).getNB(0).as(nb299)", &p2));
    CHECK_TRUE(p1.get() == p2.get());
  }
}

// ---- rpc: deterministic result reuse + cross-request coalescing ----
void TestExecuteReuseAndCoalesce() {
  std::shared_ptr<const Graph> g(RingGraph());
  auto server = std::make_unique<GraphServer>(g, nullptr, 0, 1, 1);
  CHECK_OK(server->Start(0));
  RpcConfig saved = GlobalRpcConfig();
  GlobalRpcConfig().mux = true;
  GlobalRpcConfig().mux_connections = 1;
  GlobalRpcConfig().prepared = true;
  GlobalRpcConfig().reuse_window = 8;
  auto& ctr = GlobalRpcCounters();

  CompileOptions opts;
  opts.mode = "local";
  opts.fuse_local = false;  // keep the plan multi-node for the optimizer
  GqlCompiler compiler(opts);
  std::shared_ptr<const TranslateResult> plan;
  CHECK_OK(compiler.Compile("v(roots).getNB(*).as(nb)", &plan));
  ExecuteRequest req;
  Tensor roots(DType::kU64, {2});
  roots.Flat<uint64_t>()[0] = 3;
  roots.Flat<uint64_t>()[1] = 9;
  req.inputs.emplace_back("roots", roots);
  req.nodes = plan->dag.nodes;
  req.outputs = {"nb:1"};
  ByteWriter pw, fw;
  EncodeExecutePlan(req, &pw);
  EncodeExecuteFeeds(req, &fw);
  const uint64_t pid =
      PlanContentHash(pw.buffer().data(), pw.buffer().size());

  RpcChannel ch("127.0.0.1", server->port());
  ch.set_mux(true);
  // cold call: registers + executes + installs the reuse entry
  const uint64_t hit0 = ctr.reuse_hits.load();
  const uint64_t miss0 = ctr.reuse_misses.load();
  std::vector<char> rep1, rep2;
  CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rep1, 2));
  CHECK_TRUE(ctr.reuse_misses.load() == miss0 + 1);
  // warm call: byte-identical reply straight from the window
  CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rep2, 2));
  CHECK_TRUE(ctr.reuse_hits.load() == hit0 + 1);
  CHECK_TRUE(rep1 == rep2);
  // different feeds: never served from the window (exact-byte compare)
  ExecuteRequest reqB = req;
  reqB.inputs[0].second.Flat<uint64_t>()[1] = 11;
  ByteWriter fwB;
  EncodeExecuteFeeds(reqB, &fwB);
  std::vector<char> repB;
  CHECK_OK(
      ch.CallExecutePrepared(pw.buffer(), pid, fwB.buffer(), &repB, 2));
  CHECK_TRUE(repB != rep1);

  // ownership flip purges the window (counted) — a post-flip call can
  // never be answered with a pre-flip result
  const uint64_t inv0 = ctr.reuse_invalidated.load();
  auto om = std::make_shared<OwnershipMap>();
  CHECK_OK(OwnershipMap::Decode("e1-P1-0", om.get()));
  CHECK_OK(server->SetOwnership(om));
  CHECK_TRUE(ctr.reuse_invalidated.load() >= inv0 + 2);

  // nondeterministic plan: the fast path must not engage at all
  std::shared_ptr<const TranslateResult> splan;
  CHECK_OK(compiler.Compile("v(roots).sampleNB(0, 3, -1).as(snb)", &splan));
  ExecuteRequest sreq;
  sreq.inputs.emplace_back("roots", roots);
  sreq.nodes = splan->dag.nodes;
  sreq.outputs = {"snb:1"};
  ByteWriter spw, sfw;
  EncodeExecutePlan(sreq, &spw);
  EncodeExecuteFeeds(sreq, &sfw);
  const uint64_t spid =
      PlanContentHash(spw.buffer().data(), spw.buffer().size());
  const uint64_t h1 = ctr.reuse_hits.load();
  const uint64_t m1 = ctr.reuse_misses.load();
  std::vector<char> sr1, sr2;
  CHECK_OK(
      ch.CallExecutePrepared(spw.buffer(), spid, sfw.buffer(), &sr1, 2));
  CHECK_OK(
      ch.CallExecutePrepared(spw.buffer(), spid, sfw.buffer(), &sr2, 2));
  CHECK_TRUE(ctr.reuse_hits.load() == h1);
  CHECK_TRUE(ctr.reuse_misses.load() == m1);

  // coalescing: two identical deterministic executes inside one window
  // → one shared run answers both, byte-identically
  GlobalRpcConfig().reuse_window = 0;  // isolate the coalescer
  GlobalRpcConfig().coalesce_window_us = 60000;
  const uint64_t co0 = ctr.coalesced_requests.load();
  const uint64_t cb0 = ctr.coalesce_batches.load();
  std::vector<char> ra, rb;
  std::thread t1([&] {
    CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &ra, 2));
  });
  ::usleep(5000);  // let the leader open its bucket
  std::thread t2([&] {
    CHECK_OK(ch.CallExecutePrepared(pw.buffer(), pid, fw.buffer(), &rb, 2));
  });
  t1.join();
  t2.join();
  CHECK_TRUE(ra == rb && ra == rep1);
  CHECK_TRUE(ctr.coalesced_requests.load() >= co0 + 1);
  CHECK_TRUE(ctr.coalesce_batches.load() >= cb0 + 1);

  server->Stop();
  GlobalRpcConfig() = saved;
}

}  // namespace
}  // namespace et


int main() {
  // server/client teardown races write to closing sockets on purpose
  // (hedge losers, coalesce fan-out) — EPIPE is handled, SIGPIPE kills
  ::signal(SIGPIPE, SIG_IGN);
  et::MinLogLevel() = 2;  // quiet
  et::TestPcg32Determinism();
  et::TestAliasSamplerStatistics();
  et::TestParallelForCoversAll();
  et::TestThreadPoolStress();
  et::TestThreadPoolPriorityLanes();
  et::TestRegistryServer();
  et::TestRpcMuxTransport();
  et::TestRpcHelloFallback();
  et::TestServerTraceBreakdown();
  et::TestSerdeSizingSplitSegments();
  et::TestPreparedPlanExecution();
  et::TestPlanOptimizerPasses();
  et::TestExecuteReuseAndCoalesce();
  et::TestI32OffsetGuard();
  et::TestGraphStore();
  et::TestConcurrentSampling();
  et::TestUdfResultCacheConcurrent();
  et::TestTensorSerde();
  et::TestExecutorRunsDag();
  et::TestIndexDnf();
  et::TestDumpLoadRoundtrip();
  et::TestColumnarStoreRoundtrip();
  et::TestColumnarStorePostDelta();
  et::TestWalColumnarSidecarRecovery();
  et::TestColumnarStoreHardening();
  if (et::g_failures == 0) {
    std::printf("engine_test: ALL OK\n");
    return 0;
  }
  std::fprintf(stderr, "engine_test: %d failures\n", et::g_failures);
  return 1;
}
