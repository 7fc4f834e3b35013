// Dense ranks of uint64 hashes — the native fast path for
// ops/minhash.py::pack_sketches (the primary compare's pack: 2.46e7 hashes
// at 24,576 genomes, where one `np.argsort` and the gathers and scatters
// through its permutation walk 200-300 MB at random on one core).
//
// SEMANTIC CONTRACT: a hash's rank is the number of DISTINCT hashes smaller
// than it over the whole input; equal hashes get equal ranks; ranks are
// dense 0..V-1. Exactly what the NumPy path computes, for any input (rows
// need be neither sorted nor free of repeats), so the two are compared
// byte for byte (tests/test_minhash.py).
//
// The rows are read where they lie (no concatenation) and the whole output
// matrix is written here, padding included, so that its pages are first
// touched by the threads and not by one `np.full` before them.
//
// How: the hashes are cut into up to 4096 buckets by their top bits under
// the largest hash, each thread counting and then scattering a slice of the
// input as (hash, place in the output) pairs; a bucket is sorted where it
// lies (a few thousand pairs, in cache) by whichever thread takes it next
// off a shared queue — sketches of genomes of different sizes crowd the low
// buckets, and one bucket that holds everything is merely one std::sort;
// the buckets' distinct counts are prefixed, and every rank is written to
// its place. Buckets ascend with the hash, so the ranks do.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <memory>
#include <new>
#include <system_error>
#include <thread>
#include <vector>

namespace {

constexpr int kMaxBucketBits = 12;
constexpr int64_t kPairsPerBucket = 4096;  // the size a bucket should not fall under

struct Pair {
  uint64_t hash;
  int64_t place;  // index into the padded output matrix
};

// fn(t) for every t under `threads`, each on a thread of its own but the
// caller's t = 0; where the system has no thread left to give, the caller
// does that share too
template <typename Fn>
void run_threads(int threads, Fn fn) {
  std::vector<std::thread> pool;
  pool.reserve(threads - 1);
  int started = 1;
  try {
    for (; started < threads; ++started) pool.emplace_back(fn, started);
  } catch (const std::system_error&) {
  }
  fn(0);
  for (int t = started; t < threads; ++t) fn(t);
  for (auto& th : pool) th.join();
}

int bit_length(uint64_t v) {
  int bits = 0;
  for (; v; v >>= 1) ++bits;
  return bits;
}

}  // namespace

extern "C" {

// rows[r]: row r's hashes, offsets[r+1] - offsets[r] of them (offsets is the
// running sum of the rows' lengths, n_rows + 1 long); out: the [n_rows,
// stride] matrix whose row r takes its ranks at columns 0..len(r)-1 and
// `pad` past them. Returns 0 and the vocabulary's size in *distinct_out; 1
// where that size reaches `limit` (the caller's id space; `out` then holds
// no rank); 2 where memory ran out.
int drep_rank_rows(const uint64_t* const* rows, const int64_t* offsets, int64_t n_rows,
                   int64_t stride, int32_t* out, int32_t pad, int threads, int64_t limit,
                   int64_t* distinct_out) {
  *distinct_out = 0;
  const int64_t n_hashes = offsets[n_rows];
  threads = static_cast<int>(std::max<int64_t>(1, std::min<int64_t>(threads, n_hashes)));
  try {
    // thread t's share: the hashes slice(t)..slice(t+1) of the rows laid end
    // to end, walked row by row as fn(hash, place in `out`)
    auto slice = [&](int t) { return n_hashes * t / threads; };
    auto walk = [&](int t, auto fn) {
      int64_t i = slice(t), hi = slice(t + 1);
      int64_t row = std::upper_bound(offsets, offsets + n_rows + 1, i) - offsets - 1;
      while (i < hi) {
        while (offsets[row + 1] <= i) ++row;  // rows may be empty
        const uint64_t* h = rows[row] + (i - offsets[row]);
        int64_t place = row * stride + (i - offsets[row]);
        for (int64_t end = std::min(offsets[row + 1], hi); i < end; ++i) fn(*h++, place++);
      }
    };

    std::vector<uint64_t> top(threads, 0);
    run_threads(threads, [&](int t) {
      uint64_t m = 0;
      walk(t, [&](uint64_t h, int64_t) { m = std::max(m, h); });
      top[t] = m;
      for (int64_t r = n_rows * t / threads, hi = n_rows * (t + 1) / threads; r < hi; ++r)
        std::fill(out + r * stride + (offsets[r + 1] - offsets[r]), out + (r + 1) * stride, pad);
    });
    if (n_hashes == 0) return 0;
    int bucket_bits = std::min(kMaxBucketBits, bit_length(n_hashes / kPairsPerBucket));
    const int64_t n_buckets = int64_t{1} << bucket_bits;
    // a hash's bucket is its top `bucket_bits` bits under the largest hash's
    // highest; one bucket takes every hash, and shifts nothing by 64 bits
    const int shift = std::max(0, bit_length(*std::max_element(top.begin(), top.end())) - bucket_bits);
    auto bucket_of = [=](uint64_t h) { return n_buckets > 1 ? static_cast<int64_t>(h >> shift) : 0; };

    // where each thread's pairs of each bucket start: count, then prefix
    // bucket by bucket, thread by thread inside one
    std::vector<int64_t> at(static_cast<size_t>(threads) * n_buckets, 0);
    run_threads(threads, [&](int t) {
      int64_t* count = &at[static_cast<size_t>(t) * n_buckets];
      walk(t, [&](uint64_t h, int64_t) { ++count[bucket_of(h)]; });
    });
    std::vector<int64_t> start(n_buckets + 1, 0);
    int64_t run = 0;
    for (int64_t b = 0; b < n_buckets; ++b) {
      start[b] = run;
      for (int t = 0; t < threads; ++t) {
        int64_t c = at[static_cast<size_t>(t) * n_buckets + b];
        at[static_cast<size_t>(t) * n_buckets + b] = run;
        run += c;
      }
    }
    start[n_buckets] = run;

    std::unique_ptr<Pair[]> pairs(new Pair[n_hashes]);
    run_threads(threads, [&](int t) {
      int64_t* next = &at[static_cast<size_t>(t) * n_buckets];
      walk(t, [&](uint64_t h, int64_t place) { pairs[next[bucket_of(h)]++] = {h, place}; });
    });

    // fn(b, first pair, past the last) for every bucket, whichever thread
    // takes it next
    auto each_bucket = [&](auto fn) {
      std::atomic<int64_t> queue{0};
      run_threads(threads, [&](int) {
        for (int64_t b; (b = queue.fetch_add(1, std::memory_order_relaxed)) < n_buckets;)
          fn(b, pairs.get() + start[b], pairs.get() + start[b + 1]);
      });
    };
    // sort every bucket, count its distinct hashes
    std::vector<int64_t> base(n_buckets + 1, 0);
    each_bucket([&](int64_t b, Pair* lo, Pair* hi) {
      std::sort(lo, hi, [](const Pair& x, const Pair& y) { return x.hash < y.hash; });
      for (Pair* p = lo; p < hi; ++p) base[b + 1] += (p == lo || p->hash != p[-1].hash);
    });
    for (int64_t b = 0; b < n_buckets; ++b) base[b + 1] += base[b];
    *distinct_out = base[n_buckets];
    if (base[n_buckets] >= limit) return 1;

    each_bucket([&](int64_t b, const Pair* lo, const Pair* hi) {
      int64_t rank = base[b] - 1;
      for (const Pair* p = lo; p < hi; ++p) {
        rank += (p == lo || p->hash != p[-1].hash);
        out[p->place] = static_cast<int32_t>(rank);
      }
    });
    return 0;
  } catch (const std::bad_alloc&) {
    return 2;
  }
}

}  // extern "C"
