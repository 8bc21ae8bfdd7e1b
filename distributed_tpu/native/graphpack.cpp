// graphpack: O(T+E) host-side preprocessing of a task graph for the
// level-synchronous device placement engine (ops/leveled.py).
//
// Replaces the numpy lexsort/ufunc pack (825 ms at 1M tasks) with a C++
// pass, and additionally computes topological levels so the device
// kernel needs NO dependency edges and NO indegree bookkeeping at all:
// each wave is a contiguous slice of the level-sorted task arrays.
//
// The topology pass picks its thread count from the task count and the
// host's cores (graphpack_threads).  Up to ~2M tasks its per-task
// arrays stay in cache and it runs serially, with the heavy-dependency
// folds on a sibling thread.  Beyond that every access by task id is a
// DRAM miss, one core waits on memory latency, and the pass runs on a
// pool: more cores keep more misses in flight.  Each pool thread owns a
// contiguous range of task ids and writes only its own range of every
// per-task array, so no atomics are needed, and every output is
// bit-identical to the serial pass:
//   - each thread scans the whole edge list in order and takes only the
//     edges whose endpoint it owns: the indegree, heavy-dependency and
//     dep_total folds of a task (by dst) and its out-adjacency list (by
//     src) see that task's edges in edge-list order, as one thread would;
//   - the Kahn peel is level-synchronous, and a level does not depend on
//     the order its frontier is visited in.  A wide frontier is split
//     across the pool, which hands each dependent to the owner of its
//     id for the indegree decrement; a narrow one is peeled on the
//     calling thread with no barrier;
//   - the counting sort by level scans each range in ascending task id
//     behind the counts of the lower ranges, so perm stays stable by
//     original index.
//
// Reference semantics mirrored (not copied): the heaviest-dependency
// choice corresponds to decide_worker's candidate set from who_has
// (distributed/scheduler.py:8550) and dep_total to worker_objective's
// missing-bytes term (distributed/scheduler.py:3131).

#include <algorithm>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>
#ifdef __linux__
#include <sched.h>
#endif

namespace {

// graphs from this many tasks up run on the pool.  Below it the per-task
// arrays (4 B a task each, about a dozen) stay in the host's caches, and
// a thread that scans every edge to take a share of the misses costs
// more than it saves (1M tasks on a 13-core v5e host: 3 pool threads
// took 64 ms of the serial pass's 40)
constexpr int64_t kPoolTasks = (int64_t)1 << 21;
// above it, one thread per this many tasks, at most kMaxThreads
constexpr int64_t kTasksPerThread = (int64_t)1 << 18;
constexpr int kMaxThreads = 16;
// the serial pass moves the heavy folds to a sibling thread from this
// many edges up
constexpr int64_t kSiblingEdges = (int64_t)1 << 18;
// edges a pool thread scans before it applies the ones it owns: the
// updates then run back to back, many misses in flight, instead of one
// between every few skipped edges
constexpr int kChunk = 4096;
// frontiers narrower than this are peeled on the calling thread: a
// level of a few hundred tasks costs less than waking the pool
constexpr int64_t kWideLevel = (int64_t)1 << 14;

int host_cores() {
#ifdef __linux__
    cpu_set_t set;
    if (sched_getaffinity(0, sizeof set, &set) == 0) return CPU_COUNT(&set);
#endif
    return std::max(1u, std::thread::hardware_concurrency());
}

// The calling thread plus n - 1 workers, started once per pass.
// run(fn) calls fn(p) for every p in [0, n), p = 0 on the caller, and
// returns when all have finished.
class Pool {
  public:
    explicit Pool(int n) : n_(n) {
        for (int p = 1; p < n; ++p) workers_.emplace_back(&Pool::serve, this, p);
    }
    ~Pool() {
        {
            std::lock_guard<std::mutex> lk(mu_);
            stop_ = true;
            ++gen_;
        }
        go_.notify_all();
        for (auto& w : workers_) w.join();
    }

    template <class Fn>
    void run(Fn&& fn) {
        if (n_ == 1) {
            fn(0);
            return;
        }
        {
            std::lock_guard<std::mutex> lk(mu_);
            job_ = fn;
            busy_ = n_ - 1;
            ++gen_;
        }
        go_.notify_all();
        fn(0);
        std::unique_lock<std::mutex> lk(mu_);
        done_.wait(lk, [&] { return busy_ == 0; });
    }

  private:
    void serve(int p) {
        uint64_t seen = 0;
        std::unique_lock<std::mutex> lk(mu_);
        for (;;) {
            go_.wait(lk, [&] { return gen_ != seen; });
            seen = gen_;
            if (stop_) return;
            lk.unlock();
            job_(p);  // written before gen_ moved; not rewritten until busy_ == 0
            lk.lock();
            if (--busy_ == 0) done_.notify_one();
        }
    }

    int n_;
    std::vector<std::thread> workers_;
    std::mutex mu_;
    std::condition_variable go_, done_;
    std::function<void(int)> job_;
    uint64_t gen_ = 0;
    int busy_ = 0;
    bool stop_ = false;
};

// uninitialised storage: each pool thread first-touches its own range,
// so the page faults of fresh 40 MB arrays spread over the pool too
template <class V>
std::unique_ptr<V[]> raw(int64_t n) {
    return std::unique_ptr<V[]>(new V[std::max<int64_t>(n, 1)]);
}

// [begin, end) of part p of n items split into parts
inline int64_t cut(int64_t n, int p, int parts) { return n * p / parts; }

// Per-task reductions over a task's dependencies, folded one edge
// s -> d at a time in edge order: the top-2 heaviest by out_bytes (ties
// toward the lowest source index) and the byte total.
struct HeavyFold {
    int32_t* heavy;
    int32_t* heavy2;
    float* dep_total;
    float* heavy_bytes;
    float* heavy2_bytes;

    void init(int64_t t) const {
        heavy[t] = -1;
        heavy2[t] = -1;
        dep_total[t] = 0.0f;
        heavy_bytes[t] = -1.0f;
        heavy2_bytes[t] = -1.0f;
    }
    void add(int32_t s, int32_t d, float w) const {
        dep_total[d] += w;
        if (w > heavy_bytes[d] || (w == heavy_bytes[d] && s < heavy[d])) {
            heavy2_bytes[d] = heavy_bytes[d];
            heavy2[d] = heavy[d];
            heavy_bytes[d] = w;
            heavy[d] = s;
        } else if (w > heavy2_bytes[d]
                   || (w == heavy2_bytes[d] && s < heavy2[d])) {
            heavy2_bytes[d] = w;
            heavy2[d] = s;
        }
    }
};

}  // namespace

extern "C" {

// The number of threads the topology pass runs on for T tasks here: 1
// (the serial pass) under kPoolTasks, else one per kTasksPerThread
// tasks, at most the cores this process may run on and kMaxThreads.
int32_t graphpack_threads(int64_t T) {
    if (T < kPoolTasks) return 1;
    int64_t n = std::min<int64_t>(T / kTasksPerThread, kMaxThreads);
    return (int32_t)std::max<int64_t>(1, std::min<int64_t>(n, host_cores()));
}

// The serial pass, for graphs whose per-task arrays stay in cache.  The
// edge-derived reductions split into two data-independent halves: the
// HEAVY half (top-2 heaviest deps + dep byte totals, feeding the
// transfer cost model) and the TOPOLOGY half (indegree + CSR out-degree
// counts, feeding the Kahn peel).  On multi-core hosts the heavy half
// runs on a sibling thread while this thread continues straight into
// CSR fill and the peel — the heavy outputs are only needed by the
// (later) row fill, so the join happens at return.  Both halves scan
// edges in identical order, so ties and results are bit-identical to
// the sequential pass.
static int64_t topo_serial(
    int64_t T, int64_t E,
    const float* out_bytes,
    const int32_t* src, const int32_t* dst,
    int32_t* level, int32_t* perm, int32_t* heavy, int32_t* heavy2,
    float* dep_total, int32_t* offsets,
    int32_t* indeg_out, int32_t* inv)
{
    std::vector<int32_t> indeg(T, 0);
    // int32 CSR vectors: E < 2^31 by construction (int32 edge indices),
    // and halving the pointer arrays' traffic matters — the peel is
    // memory-bound
    std::vector<int32_t> outptr(T + 1, 0);

    auto heavy_pass = [&]() {
        std::vector<float> heavy_bytes(T, -1.0f);
        std::vector<float> heavy2_bytes(T, -1.0f);
        const HeavyFold fold{heavy, heavy2, dep_total, heavy_bytes.data(),
                             heavy2_bytes.data()};
        for (int64_t t = 0; t < T; ++t) {
            heavy[t] = -1;
            heavy2[t] = -1;
            dep_total[t] = 0.0f;
        }
        for (int64_t e = 0; e < E; ++e) {
            int32_t s = src[e], d = dst[e];
            if (s < 0 || s >= T || d < 0 || d >= T || s == d) continue;
            fold.add(s, d, out_bytes[s]);
        }
    };
    std::thread heavy_thread;
    if (E >= kSiblingEdges && host_cores() > 1) {
        heavy_thread = std::thread(heavy_pass);
    } else {
        heavy_pass();
    }

    for (int64_t t = 0; t < T; ++t) level[t] = -1;
    for (int64_t e = 0; e < E; ++e) {
        int32_t s = src[e], d = dst[e];
        if (s < 0 || s >= T || d < 0 || d >= T || s == d) continue;
        indeg[d] += 1;
        outptr[s + 1] += 1;
    }
    if (indeg_out != nullptr)
        std::memcpy(indeg_out, indeg.data(), T * sizeof(int32_t));

    // CSR out-adjacency fill (second and last edge pass)
    for (int64_t t = 0; t < T; ++t) outptr[t + 1] += outptr[t];
    std::vector<int32_t> outadj(outptr[T]);
    {
        std::vector<int32_t> fill(outptr.begin(), outptr.end() - 1);
        for (int64_t e = 0; e < E; ++e) {
            int32_t s = src[e], d = dst[e];
            if (s < 0 || s >= T || d < 0 || d >= T || s == d) continue;
            outadj[fill[s]++] = d;
        }
    }

    // Kahn's algorithm, level-synchronous.  Frontier order within a
    // level does NOT matter for level assignment, so no per-level sort
    // happens here (the old per-level std::sort dominated the pack at
    // 1M tasks); the stable (level, original-index) permutation is
    // rebuilt afterwards with one counting sort over levels.
    std::vector<int32_t> frontier, next;
    frontier.reserve(T);
    next.reserve(T);
    for (int64_t t = 0; t < T; ++t)
        if (indeg[t] == 0) frontier.push_back((int32_t)t);

    int64_t placed = 0, n_levels = 0;
    while (!frontier.empty()) {
        for (int32_t t : frontier) level[t] = (int32_t)n_levels;
        placed += (int64_t)frontier.size();
        next.clear();
        for (int32_t t : frontier)
            for (int32_t j = outptr[t]; j < outptr[t + 1]; ++j)
                if (--indeg[outadj[j]] == 0) next.push_back(outadj[j]);
        frontier.swap(next);
        ++n_levels;
    }
    if (placed != T) {  // cycle
        if (heavy_thread.joinable()) heavy_thread.join();
        return -1;
    }

    // counting sort by level; scanning tasks in ascending original
    // index keeps the within-level order stable by construction
    std::vector<int64_t> fill(n_levels + 1, 0);
    for (int64_t t = 0; t < T; ++t) fill[level[t] + 1] += 1;
    for (int64_t l = 0; l < n_levels; ++l) fill[l + 1] += fill[l];
    for (int64_t l = 0; l <= n_levels; ++l) offsets[l] = (int32_t)fill[l];
    for (int64_t t = 0; t < T; ++t) perm[fill[level[t]]++] = (int32_t)t;
    if (inv != nullptr)
        for (int64_t i = 0; i < T; ++i) inv[perm[i]] = (int32_t)i;
    if (heavy_thread.joinable()) heavy_thread.join();
    return n_levels;
}

// The pooled pass on P >= 2 threads, for graphs past the host's caches
// (the file's header says how each phase splits).  Same outputs as
// topo_serial, bit for bit.
static int64_t topo_pooled(
    int P, int64_t T, int64_t E,
    const float* out_bytes,
    const int32_t* src, const int32_t* dst,
    int32_t* level, int32_t* perm, int32_t* heavy, int32_t* heavy2,
    float* dep_total, int32_t* offsets,
    int32_t* indeg_out, int32_t* inv)
{
    Pool pool(P);
    auto valid = [T](int32_t s, int32_t d) {
        return s >= 0 && s < T && d >= 0 && d < T && s != d;
    };
    auto indeg = raw<int32_t>(T);
    auto outptr = raw<int32_t>(T + 1);
    auto fill = raw<int32_t>(T);
    auto heavy_bytes = raw<float>(T);
    auto heavy2_bytes = raw<float>(T);
    const HeavyFold fold{heavy, heavy2, dep_total, heavy_bytes.get(),
                         heavy2_bytes.get()};

    // edge pass 1, by owner of dst: indegree and the heavy folds; by
    // owner of src: out-degree
    std::vector<int64_t> part(P + 1, 0);
    pool.run([&](int p) {
        const int64_t a = cut(T, p, P), b = cut(T, p + 1, P);
        for (int64_t t = a; t < b; ++t) {
            level[t] = -1;
            indeg[t] = 0;
            outptr[t + 1] = 0;
            fold.init(t);
        }
        std::vector<int32_t> by_src(kChunk), dep_s(kChunk), dep_d(kChunk);
        for (int64_t e0 = 0; e0 < E; e0 += kChunk) {
            int n_src = 0, n_dst = 0;
            for (int64_t e = e0; e < std::min(E, e0 + kChunk); ++e) {
                int32_t s = src[e], d = dst[e];
                if (!valid(s, d)) continue;
                by_src[n_src] = s;
                n_src += s >= a && s < b;
                dep_s[n_dst] = s;
                dep_d[n_dst] = d;
                n_dst += d >= a && d < b;
            }
            for (int i = 0; i < n_src; ++i) outptr[by_src[i] + 1] += 1;
            for (int i = 0; i < n_dst; ++i) {
                indeg[dep_d[i]] += 1;
                fold.add(dep_s[i], dep_d[i], out_bytes[dep_s[i]]);
            }
        }
        int64_t sum = 0;
        for (int64_t t = a; t < b; ++t) sum += outptr[t + 1];
        part[p + 1] = sum;
    });

    // out-degree prefix sum, blocked by range: each range starts after
    // the out-edges of the ranges below it
    for (int p = 0; p < P; ++p) part[p + 1] += part[p];
    outptr[0] = 0;
    pool.run([&](int p) {
        const int64_t a = cut(T, p, P), b = cut(T, p + 1, P);
        int64_t at = part[p];
        for (int64_t t = a; t < b; ++t) {
            fill[t] = (int32_t)at;
            at += outptr[t + 1];
            outptr[t + 1] = (int32_t)at;
        }
        if (indeg_out != nullptr)
            std::memcpy(indeg_out + a, &indeg[a], (b - a) * sizeof(int32_t));
    });

    // edge pass 2, by owner of src: each out-adjacency list in edge order
    auto outadj = raw<int32_t>(part[P]);
    pool.run([&](int p) {
        const int64_t a = cut(T, p, P), b = cut(T, p + 1, P);
        for (int64_t e = 0; e < E; ++e) {
            int32_t s = src[e], d = dst[e];
            if (s >= a && s < b && valid(s, d)) outadj[fill[s]++] = d;
        }
    });
    fill.reset();

    // the level-synchronous peel: each thread collects what it makes
    // ready in local[p]; gather concatenates them into the next frontier
    std::vector<std::vector<int32_t>> local(P);
    // handoff[p * P + q]: dependents thread p reached whose ids q owns
    std::vector<std::vector<int32_t>> handoff((size_t)P * P);
    auto gather = [&](std::vector<int32_t>& out) {
        size_t n = 0;
        for (auto& v : local) n += v.size();
        out.resize(n);
        n = 0;
        for (auto& v : local) {
            std::memcpy(out.data() + n, v.data(), v.size() * sizeof(int32_t));
            n += v.size();
        }
    };
    std::vector<int32_t> frontier, next;
    frontier.reserve(T);
    next.reserve(T);
    pool.run([&](int p) {
        const int64_t a = cut(T, p, P), b = cut(T, p + 1, P);
        for (int64_t t = a; t < b; ++t)
            if (indeg[t] == 0) local[p].push_back((int32_t)t);
    });
    gather(frontier);

    int64_t placed = 0, n_levels = 0;
    while (!frontier.empty()) {
        const int64_t n = (int64_t)frontier.size();
        const int32_t lv = (int32_t)n_levels;
        if (n < kWideLevel) {
            next.clear();
            for (int32_t t : frontier) {
                level[t] = lv;
                for (int32_t j = outptr[t]; j < outptr[t + 1]; ++j)
                    if (--indeg[outadj[j]] == 0) next.push_back(outadj[j]);
            }
        } else {
            pool.run([&](int p) {
                std::vector<int32_t>* out = &handoff[(size_t)p * P];
                for (int q = 0; q < P; ++q) out[q].clear();
                const int64_t a = cut(n, p, P), b = cut(n, p + 1, P);
                for (int64_t i = a; i < b; ++i) {
                    int32_t t = frontier[i];
                    level[t] = lv;
                    for (int32_t j = outptr[t]; j < outptr[t + 1]; ++j) {
                        int32_t x = outadj[j];
                        // the q with cut(T, q, P) <= x < cut(T, q + 1, P)
                        out[((int64_t)(x + 1) * P - 1) / T].push_back(x);
                    }
                }
            });
            pool.run([&](int p) {
                local[p].clear();
                for (int q = 0; q < P; ++q)
                    for (int32_t x : handoff[(size_t)q * P + p])
                        if (--indeg[x] == 0) local[p].push_back(x);
            });
            gather(next);
        }
        placed += n;
        frontier.swap(next);
        ++n_levels;
    }
    if (placed != T) return -1;  // cycle

    // counting sort by level.  Per-range level histograms cost P x
    // n_levels counters; a deep graph (a chain has T levels) sorts on
    // one range instead.
    const int S = n_levels * P <= T ? P : 1;
    std::vector<int32_t> hist((size_t)S * n_levels, 0);
    auto count = [&](int p) {
        int32_t* h = &hist[(size_t)p * n_levels];
        const int64_t a = cut(T, p, S), b = cut(T, p + 1, S);
        for (int64_t t = a; t < b; ++t) h[level[t]] += 1;
    };
    auto scatter = [&](int p) {
        int32_t* at = &hist[(size_t)p * n_levels];
        const int64_t a = cut(T, p, S), b = cut(T, p + 1, S);
        for (int64_t t = a; t < b; ++t) {
            int32_t i = at[level[t]]++;
            perm[i] = (int32_t)t;
            if (inv != nullptr) inv[t] = i;
        }
    };
    if (S == P) pool.run(count); else count(0);
    // turn counts into each range's start within each level: a level's
    // block holds range 0's tasks, then range 1's, ...
    int32_t at = 0;
    for (int64_t l = 0; l < n_levels; ++l) {
        offsets[l] = at;
        for (int p = 0; p < S; ++p) {
            int32_t c = hist[(size_t)p * n_levels + l];
            hist[(size_t)p * n_levels + l] = at;
            at += c;
        }
    }
    offsets[n_levels] = at;
    if (S == P) pool.run(scatter); else scatter(0);
    return n_levels;
}

// Returns the number of levels (>=0) on success, -1 if the graph has a
// cycle (some tasks never became ready).  All output buffers are
// caller-allocated with length T (offsets: T+1).  Edges with an
// endpoint out of [0, T) and self-loops are ignored.
//
//   level[t]     topological level of task t (0 = no dependencies)
//   perm[i]      original index of the i-th task in (level, index) order
//   heavy[t]     dependency of t with the largest out_bytes (-1 if none;
//                ties broken toward the lowest source index)
//   heavy2[t]    second-largest dependency by out_bytes (-1 if <2 deps);
//                the placement kernel weighs BOTH producers' workers as
//                locality candidates (join-shaped tasks — tensordot,
//                merges — have two comparable inputs)
//   dep_total[t] sum of out_bytes over t's dependencies, in edge order
//   offsets[l]   start of level l in perm; offsets[n_levels] == T
//   indeg[t]     number of t's dependencies (nullable)
//   inv[t]       position of t in perm (nullable)
//
// ``threads`` is the pool size, 1 the serial pass; 0 means
// graphpack_threads(T).
static int64_t topo_core(
    int64_t T, int64_t E,
    const float* out_bytes,
    const int32_t* src, const int32_t* dst,
    int32_t* level, int32_t* perm, int32_t* heavy, int32_t* heavy2,
    float* dep_total, int32_t* offsets,
    int32_t* indeg_out, int32_t* inv, int32_t threads)
{
    if (T <= 0) return 0;
    const int P = threads > 0 ? threads : graphpack_threads(T);
    if (P == 1)
        return topo_serial(T, E, out_bytes, src, dst, level, perm, heavy,
                           heavy2, dep_total, offsets, indeg_out, inv);
    return topo_pooled(P, T, E, out_bytes, src, dst, level, perm, heavy,
                       heavy2, dep_total, offsets, indeg_out, inv);
}

// Streamed-pack phase 1: topology only.  Emits everything the python
// driver needs to plan waves and allocate device buffers (level, perm,
// offsets) plus the original-order per-task reductions the fill pass
// consumes (heavy, heavy2, dep_total, indeg) and the inverse
// permutation.  Returns n_levels, -1 on cycle.  All buffers
// caller-allocated, length T (offsets: T+1).  ``threads`` as topo_core.
int64_t graphpack_topo(
    int64_t T, int64_t E,
    const float* out_bytes,
    const int32_t* src, const int32_t* dst,
    int32_t* level, int32_t* perm, int32_t* offsets,
    int32_t* heavy, int32_t* heavy2, float* dep_total,
    int32_t* indeg, int32_t* inv, int32_t threads)
{
    return topo_core(T, E, out_bytes, src, dst, level, perm, heavy, heavy2,
                     dep_total, offsets, indeg, inv, threads);
}

// Streamed-pack phase 2: fill sorted rows [i0, i1) of the per-task
// arrays the device kernel consumes.  Chunked so the python driver can
// overlap later fills with the (async) upload of earlier chunks and with
// the device's work on earlier waves.
void graphpack_fill(
    int64_t i0, int64_t i1,
    const float* durations, const float* out_bytes,
    const int32_t* perm, const int32_t* inv,
    const int32_t* heavy, const int32_t* heavy2,
    const float* dep_total, const int32_t* indeg,
    double inv_bandwidth, double latency,
    float* dur_s, int32_t* heavy_s, int32_t* heavy2_s,
    float* xp_s, float* xp2_s, float* xa_s)
{
    float ibw = (float)inv_bandwidth;
    float lat = (float)latency;
    for (int64_t i = i0; i < i1; ++i) {
        int32_t t = perm[i];
        dur_s[i] = durations[t];
        int32_t h = heavy[t];
        int32_t h2 = heavy2[t];
        heavy_s[i] = h >= 0 ? inv[h] : -1;
        heavy2_s[i] = h2 >= 0 ? inv[h2] : -1;
        float hb = h >= 0 ? out_bytes[h] : 0.0f;
        float h2b = h2 >= 0 ? out_bytes[h2] : 0.0f;
        float deg = (float)indeg[t];
        float extra = lat * (deg > 1.0f ? deg - 1.0f : 0.0f);
        xa_s[i] = dep_total[t] * ibw + lat * deg;
        xp_s[i] = (dep_total[t] - hb) * ibw + extra;
        xp2_s[i] = (dep_total[t] - h2b) * ibw + extra;
    }
}

// Full pack: the topology pass plus the level-sorted, remapped per-task
// arrays the device kernel consumes, so the hot path does no numpy
// fancy indexing at all.  Outputs (length T, caller-allocated):
//   dur_s[i]    duration of sorted task i
//   heavy_s[i]  heaviest dep of sorted task i as a SORTED index (-1 none)
//   heavy2_s[i] second-heaviest dep as a SORTED index (-1 none)
//   xp_s[i]     transfer seconds if co-located with the heavy dep
//   xp2_s[i]    transfer seconds if co-located with the 2nd-heaviest dep
//   xa_s[i]     transfer seconds if placed anywhere else
// plus level/perm/offsets as in graphpack_topo.
// ``latency`` is the per-remote-dependency round-trip cost folded into
// the transfer model here (rather than via numpy post-passes over 1M-row
// arrays, which cost more than the whole pack): co-location with a dep
// saves one latency; any other placement pays one per dependency.
int64_t graphpack_full(
    int64_t T, int64_t E,
    const float* durations, const float* out_bytes,
    const int32_t* src, const int32_t* dst,
    double inv_bandwidth, double latency,
    int32_t* level, int32_t* perm, int32_t* offsets,
    float* dur_s, int32_t* heavy_s, int32_t* heavy2_s,
    float* xp_s, float* xp2_s, float* xa_s)
{
    std::vector<int32_t> heavy(T), heavy2(T), indeg(T), inv(T);
    std::vector<float> dep_total(T);
    int64_t n_levels = topo_core(
        T, E, out_bytes, src, dst, level, perm, heavy.data(), heavy2.data(),
        dep_total.data(), offsets, indeg.data(), inv.data(), 0);
    if (n_levels < 0) return -1;
    graphpack_fill(0, T, durations, out_bytes, perm, inv.data(),
                   heavy.data(), heavy2.data(), dep_total.data(),
                   indeg.data(), inv_bandwidth, latency,
                   dur_s, heavy_s, heavy2_s, xp_s, xp2_s, xa_s);
    return n_levels;
}

// Post-pass for the device placement result: one cache-friendly sweep
// replaces four numpy passes + two 1M-row fancy-index scatters
// (packed -> assignment/choice in ORIGINAL task order).
//   packed_h[i] = (assign_sorted[i] + 1) * 4 + choice_sorted[i]
void unpack_assignment(
    int64_t T,
    const int32_t* packed_h, const int32_t* perm,
    int32_t* assignment, int8_t* choice)
{
    for (int64_t i = 0; i < T; ++i) {
        int32_t v = packed_h[i];
        int32_t t = perm[i];
        assignment[t] = v / 4 - 1;
        choice[t] = (int8_t)(v & 3);
    }
}

}  // extern "C"
