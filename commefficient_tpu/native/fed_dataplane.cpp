// Native federated data-plane: round-batch assembly + threaded
// prefetch ring.
//
// The reference's host data path is worker processes fed by
// multiprocessing queues (fed_aggregator.py:137-158, SURVEY.md §2.9);
// its per-sample transform work rides torchvision's C++ kernels. This
// is the TPU build's equivalent native component: the per-round
// gather/augment/pad of (W, B, H, W, C) client batches runs here in
// C++ (GIL-free, off the Python hot loop), with a bounded ring of
// pre-assembled rounds so host data prep overlaps device steps. A ring
// and its worker threads live as long as the loader that made them:
// creating one first-touches depth x one round of output (1.09 ms/MB
// on the benchmark's host), so an epoch that ends or is abandoned
// resets the ring (cet_ring_reset) and does not rebuild it.
//
// Augmentations implemented (the CIFAR/FEMNIST stacks,
// data/transforms.py): uint8->float scaling, reflect-pad random crop,
// horizontal flip, per-channel normalize. Randomness is splitmix64 on
// (seed, slot, sample) — deterministic regardless of thread schedule.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image).

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <thread>
#include <vector>

#ifdef __linux__
#include <pthread.h>
#endif

namespace {

struct DataplaneCfg {
  const uint8_t* img_u8;   // one of img_u8 / img_f32 non-null
  const float* img_f32;    // values already in [0,1]
  const int32_t* targets;
  int64_t n_rows;          // dataset size (bounds-checked gathers)
  int H, W, C;             // per-image shape (HWC)
  int slots, B;            // round geometry: slots x B samples
  int crop_pad;            // 0 = no random crop
  int do_flip;             // 0/1 horizontal flip
  float mean[8], stdev[8]; // per-channel (C <= 8)
};

inline uint64_t splitmix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ULL;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  return x ^ (x >> 31);
}

inline int reflect_idx(int v, int n) {
  // numpy "reflect" (no edge duplication)
  if (v < 0) v = -v;
  if (v >= n) v = 2 * n - 2 - v;
  return v;
}

inline float load_px(const DataplaneCfg& c, int64_t row, int y, int x,
                     int ch) {
  int64_t off =
      ((row * c.H + y) * (int64_t)c.W + x) * c.C + ch;
  return c.img_u8 ? (float)c.img_u8[off] * (1.0f / 255.0f)
                  : c.img_f32[off];
}

// Assemble one (slots, B, H, W, C) round into out_x/out_y/out_mask.
// indices: int64[slots*B], -1 marks padding. Returns the count of
// out-of-range (row >= n_rows) indices, which are emitted as padding
// — callers treat nonzero as an error (the Python loader would have
// raised IndexError; silence here would mean garbage heap reads).
int fill_round(const DataplaneCfg& c, const int64_t* indices,
               uint64_t seed, float* out_x, int32_t* out_y,
               float* out_m) {
  const int H = c.H, W = c.W, C = c.C, p = c.crop_pad;
  const int64_t img_elems = (int64_t)H * W * C;
  int oob = 0;
  for (int s = 0; s < c.slots; ++s) {
    for (int b = 0; b < c.B; ++b) {
      const int64_t row = indices[(int64_t)s * c.B + b];
      float* dst = out_x + ((int64_t)s * c.B + b) * img_elems;
      int32_t* ydst = out_y + (int64_t)s * c.B + b;
      float* mdst = out_m + (int64_t)s * c.B + b;
      if (row < 0 || row >= c.n_rows) {
        if (row >= c.n_rows) ++oob;
        std::memset(dst, 0, sizeof(float) * img_elems);
        *ydst = 0;
        *mdst = 0.0f;
        continue;
      }
      *ydst = c.targets[row];
      *mdst = 1.0f;
      uint64_t r =
          splitmix64(seed ^ splitmix64(((uint64_t)s << 32) | (uint64_t)b));
      int ci = 0, cj = 0, flip = 0;
      if (p > 0) {
        ci = (int)(r % (uint64_t)(2 * p + 1));
        r = splitmix64(r);
        cj = (int)(r % (uint64_t)(2 * p + 1));
        r = splitmix64(r);
      }
      if (c.do_flip) flip = (int)(r & 1u);
      for (int y = 0; y < H; ++y) {
        const int sy = p > 0 ? reflect_idx(y + ci - p, H) : y;
        for (int x = 0; x < W; ++x) {
          int xx = flip ? (W - 1 - x) : x;
          const int sx = p > 0 ? reflect_idx(xx + cj - p, W) : xx;
          float* px = dst + ((int64_t)y * W + x) * C;
          for (int ch = 0; ch < C; ++ch) {
            px[ch] = (load_px(c, row, sy, sx, ch) - c.mean[ch]) /
                     c.stdev[ch];
          }
        }
      }
    }
  }
  return oob;
}

struct Spec {
  uint64_t seq;
  uint64_t seed;
  std::vector<int64_t> indices;
};

struct Ring {
  DataplaneCfg cfg;
  int depth;
  int64_t round_elems;  // floats in x per round
  int64_t round_n;      // slots*B
  std::vector<float> x;
  std::vector<int32_t> y;
  std::vector<float> m;
  std::vector<uint64_t> slot_seq;
  std::vector<int> state;  // 0 free, 1 filling, 2 ready
  std::deque<Spec> specs;
  uint64_t submit_seq = 0;
  uint64_t pop_seq = 0;
  bool stop = false;
  std::mutex mu;
  std::condition_variable cv_work, cv_ready, cv_space;
  std::vector<std::thread> workers;
  std::atomic<long long> oob{0};
};

void worker_loop(Ring* rg) {
  for (;;) {
    Spec spec;
    int slot;
    {
      std::unique_lock<std::mutex> lk(rg->mu);
      rg->cv_work.wait(lk, [&] {
        if (rg->stop) return true;
        if (rg->specs.empty()) return false;
        int sl = (int)(rg->specs.front().seq % (uint64_t)rg->depth);
        return rg->state[sl] == 0;
      });
      if (rg->stop) return;
      spec = std::move(rg->specs.front());
      rg->specs.pop_front();
      slot = (int)(spec.seq % (uint64_t)rg->depth);
      rg->state[slot] = 1;
      rg->slot_seq[slot] = spec.seq;
    }
    rg->cv_space.notify_all();
    int oob = fill_round(
        rg->cfg, spec.indices.data(), spec.seed,
        rg->x.data() + (int64_t)slot * rg->round_elems,
        rg->y.data() + (int64_t)slot * rg->round_n,
        rg->m.data() + (int64_t)slot * rg->round_n);
    if (oob) rg->oob += oob;
    {
      std::lock_guard<std::mutex> lk(rg->mu);
      rg->state[slot] = 2;
    }
    rg->cv_ready.notify_all();
  }
}

}  // namespace

extern "C" {

// ---- one-shot API ----------------------------------------------------

// Returns the number of out-of-range indices (0 = success).
int cet_assemble_round(const uint8_t* img_u8, const float* img_f32,
                       const int32_t* targets, int64_t n_rows,
                       int H, int W, int C,
                       int slots, int B, int crop_pad, int do_flip,
                       const float* mean, const float* stdev,
                       const int64_t* indices, uint64_t seed,
                       float* out_x, int32_t* out_y, float* out_m) {
  DataplaneCfg c{};
  c.img_u8 = img_u8;
  c.img_f32 = img_f32;
  c.targets = targets;
  c.n_rows = n_rows;
  c.H = H; c.W = W; c.C = C;
  c.slots = slots; c.B = B;
  c.crop_pad = crop_pad; c.do_flip = do_flip;
  for (int i = 0; i < C && i < 8; ++i) {
    c.mean[i] = mean[i];
    c.stdev[i] = stdev[i];
  }
  return fill_round(c, indices, seed, out_x, out_y, out_m);
}

// ---- prefetch ring ---------------------------------------------------

void* cet_ring_create(const uint8_t* img_u8, const float* img_f32,
                      const int32_t* targets, int64_t n_rows,
                      int H, int W, int C,
                      int slots, int B, int crop_pad, int do_flip,
                      const float* mean, const float* stdev, int depth,
                      int n_threads) {
  Ring* rg = new Ring();
  rg->cfg.img_u8 = img_u8;
  rg->cfg.img_f32 = img_f32;
  rg->cfg.targets = targets;
  rg->cfg.n_rows = n_rows;
  rg->cfg.H = H; rg->cfg.W = W; rg->cfg.C = C;
  rg->cfg.slots = slots; rg->cfg.B = B;
  rg->cfg.crop_pad = crop_pad; rg->cfg.do_flip = do_flip;
  for (int i = 0; i < C && i < 8; ++i) {
    rg->cfg.mean[i] = mean[i];
    rg->cfg.stdev[i] = stdev[i];
  }
  rg->depth = depth;
  rg->round_n = (int64_t)slots * B;
  rg->round_elems = rg->round_n * H * W * C;
  rg->x.resize((size_t)depth * rg->round_elems);
  rg->y.resize((size_t)depth * rg->round_n);
  rg->m.resize((size_t)depth * rg->round_n);
  rg->slot_seq.assign(depth, 0);
  rg->state.assign(depth, 0);
  if (n_threads < 1) n_threads = 1;
  for (int i = 0; i < n_threads; ++i) {
    rg->workers.emplace_back(worker_loop, rg);
#ifdef __linux__
    // /proc/<pid>/task/*/comm and profiler traces show the name
    pthread_setname_np(rg->workers.back().native_handle(), "cet-ring");
#endif
  }
  return rg;
}

// Blocks while the spec backlog is >= 2*depth (bounded memory).
void cet_ring_submit(void* h, const int64_t* indices, uint64_t seed) {
  Ring* rg = (Ring*)h;
  Spec spec;
  spec.seed = seed;
  spec.indices.assign(indices, indices + rg->round_n);
  {
    std::unique_lock<std::mutex> lk(rg->mu);
    rg->cv_space.wait(lk, [&] {
      return rg->stop ||
             rg->specs.size() < (size_t)(2 * rg->depth);
    });
    if (rg->stop) return;
    spec.seq = rg->submit_seq++;
    rg->specs.push_back(std::move(spec));
  }
  rg->cv_work.notify_all();
}

// Pops rounds strictly in submission order. Returns the seq popped,
// or -1 if the ring was stopped.
int64_t cet_ring_pop(void* h, float* out_x, int32_t* out_y,
                     float* out_m) {
  Ring* rg = (Ring*)h;
  int slot;
  uint64_t seq;
  {
    std::unique_lock<std::mutex> lk(rg->mu);
    seq = rg->pop_seq;
    slot = (int)(seq % (uint64_t)rg->depth);
    rg->cv_ready.wait(lk, [&] {
      return rg->stop ||
             (rg->state[slot] == 2 && rg->slot_seq[slot] == seq);
    });
    if (rg->stop) return -1;
  }
  std::memcpy(out_x, rg->x.data() + (int64_t)slot * rg->round_elems,
              sizeof(float) * rg->round_elems);
  std::memcpy(out_y, rg->y.data() + (int64_t)slot * rg->round_n,
              sizeof(int32_t) * rg->round_n);
  std::memcpy(out_m, rg->m.data() + (int64_t)slot * rg->round_n,
              sizeof(float) * rg->round_n);
  {
    std::lock_guard<std::mutex> lk(rg->mu);
    rg->state[slot] = 0;
    rg->pop_seq++;
  }
  rg->cv_work.notify_all();
  return (int64_t)seq;
}

// Out-of-range index count over the rounds assembled since the ring
// was made or last reset.
long long cet_ring_oob(void* h) {
  return ((Ring*)h)->oob.load();
}

// Empties the ring for the next epoch: queued specs are discarded,
// slots a worker is filling are waited for, every slot is freed and
// the sequence starts again at 0. Called by the one thread that
// submits and pops, between its calls.
void cet_ring_reset(void* h) {
  Ring* rg = (Ring*)h;
  {
    std::unique_lock<std::mutex> lk(rg->mu);
    rg->specs.clear();
    rg->cv_ready.wait(lk, [&] {
      if (rg->stop) return true;
      for (int st : rg->state)
        if (st == 1) return false;
      return true;
    });
    rg->state.assign(rg->depth, 0);
    rg->submit_seq = rg->pop_seq = 0;
    rg->oob = 0;
  }
  rg->cv_space.notify_all();
}

void cet_ring_destroy(void* h) {
  Ring* rg = (Ring*)h;
  {
    std::lock_guard<std::mutex> lk(rg->mu);
    rg->stop = true;
  }
  rg->cv_work.notify_all();
  rg->cv_ready.notify_all();
  rg->cv_space.notify_all();
  for (auto& t : rg->workers) t.join();
  delete rg;
}

}  // extern "C"
