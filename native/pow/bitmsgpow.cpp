// CPU proof-of-work solver: multithreaded double-SHA512 nonce search.
//
// Role equivalent of the reference's src/bitmsghash/bitmsghash.cpp
// (pthread strided nonce search), re-implemented self-contained:
// FIPS 180-4 SHA-512 specialized for the two fixed block shapes the
// trial needs (72-byte message, 64-byte digest), no OpenSSL dependency.
//
// Exported C ABI (loaded via ctypes from pybitmessage_tpu/pow/native.py):
//   tpu_bm_pow_solve(initial_hash[64], target, start_nonce, num_threads,
//                    stop_flag, trials_out, found_out) -> winning nonce;
//   *found_out distinguishes "found" from "interrupted" so every u64
//   value (including 2^64-1) is a representable nonce.

#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

typedef uint64_t u64;

static const u64 K[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

static const u64 H0[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};

static inline u64 rotr(u64 x, int n) { return (x >> n) | (x << (64 - n)); }
static inline u64 Ch(u64 e, u64 f, u64 g) { return (e & f) ^ (~e & g); }
static inline u64 Maj(u64 a, u64 b, u64 c) {
  return (a & b) ^ (a & c) ^ (b & c);
}
static inline u64 S0(u64 x) { return rotr(x, 28) ^ rotr(x, 34) ^ rotr(x, 39); }
static inline u64 S1(u64 x) { return rotr(x, 14) ^ rotr(x, 18) ^ rotr(x, 41); }
static inline u64 s0(u64 x) { return rotr(x, 1) ^ rotr(x, 8) ^ (x >> 7); }
static inline u64 s1(u64 x) { return rotr(x, 19) ^ rotr(x, 61) ^ (x >> 6); }

// One compression over a prepared 16-word block; state updated in place.
static void compress(u64 state[8], const u64 block[16]) {
  u64 w[80];
  std::memcpy(w, block, 16 * sizeof(u64));
  for (int t = 16; t < 80; ++t)
    w[t] = s1(w[t - 2]) + w[t - 7] + s0(w[t - 15]) + w[t - 16];
  u64 a = state[0], b = state[1], c = state[2], d = state[3];
  u64 e = state[4], f = state[5], g = state[6], h = state[7];
  for (int t = 0; t < 80; ++t) {
    u64 t1 = h + S1(e) + Ch(e, f, g) + K[t] + w[t];
    u64 t2 = S0(a) + Maj(a, b, c);
    h = g; g = f; f = e; e = d + t1;
    d = c; c = b; b = a; a = t1 + t2;
  }
  state[0] += a; state[1] += b; state[2] += c; state[3] += d;
  state[4] += e; state[5] += f; state[6] += g; state[7] += h;
}

// Trial value: first 8 bytes (big-endian u64) of
// SHA512(SHA512(nonce_be || initial_hash)).
static u64 trial(u64 nonce, const u64 ih[8]) {
  // block 1: 72-byte message, single padded block
  u64 block[16];
  block[0] = nonce;
  for (int i = 0; i < 8; ++i) block[1 + i] = ih[i];
  block[9] = 0x8000000000000000ULL;
  for (int i = 10; i < 15; ++i) block[i] = 0;
  block[15] = 576;  // 72 bytes * 8 bits
  u64 st[8];
  std::memcpy(st, H0, sizeof(st));
  compress(st, block);
  // block 2: the 64-byte digest
  for (int i = 0; i < 8; ++i) block[i] = st[i];
  block[8] = 0x8000000000000000ULL;
  for (int i = 9; i < 15; ++i) block[i] = 0;
  block[15] = 512;
  u64 st2[8];
  std::memcpy(st2, H0, sizeof(st2));
  compress(st2, block);
  return st2[0];
}

struct SearchShared {
  std::atomic<int> found{0};
  std::atomic<u64> winner{UINT64_MAX};
  std::atomic<u64> trials{0};
};

static void search_thread(int tid, int nthreads, const u64* ih, u64 target,
                          u64 start, const volatile int* stop_flag,
                          SearchShared* sh) {
  u64 nonce = start + (u64)tid;
  u64 local = 0;
  while (!sh->found.load(std::memory_order_relaxed)) {
    if ((local & 0x3FF) == 0) {  // poll stop every 1024 trials
      if (stop_flag && *stop_flag) break;
    }
    if (trial(nonce, ih) <= target) {
      ++local;  // the winning trial is a trial: a solve never reports 0
      // first hit wins; record the smallest winning nonce seen
      u64 prev = sh->winner.load();
      while (nonce < prev &&
             !sh->winner.compare_exchange_weak(prev, nonce)) {
      }
      sh->found.store(1, std::memory_order_relaxed);
      break;
    }
    nonce += (u64)nthreads;
    ++local;
  }
  sh->trials.fetch_add(local, std::memory_order_relaxed);
}

}  // namespace

extern "C" {

// Returns the winning nonce when *found_out is set to 1; when the
// search was interrupted via *stop_flag first, *found_out is 0 and the
// return value is meaningless.  trials_out (optional) receives the
// total trial count.
uint64_t tpu_bm_pow_solve(const uint8_t* initial_hash, uint64_t target,
                          uint64_t start_nonce, int num_threads,
                          const volatile int* stop_flag,
                          uint64_t* trials_out, int* found_out) {
  if (num_threads <= 0) {
    num_threads = (int)std::thread::hardware_concurrency();
    if (num_threads <= 0) num_threads = 1;
  }
  u64 ih[8];
  for (int i = 0; i < 8; ++i) {
    u64 w = 0;
    for (int j = 0; j < 8; ++j) w = (w << 8) | initial_hash[i * 8 + j];
    ih[i] = w;
  }
  SearchShared sh;
  std::vector<std::thread> threads;
  threads.reserve(num_threads);
  for (int t = 0; t < num_threads; ++t)
    threads.emplace_back(search_thread, t, num_threads, ih, target,
                         start_nonce, stop_flag, &sh);
  for (auto& th : threads) th.join();
  if (trials_out) *trials_out = sh.trials.load();
  int found = sh.found.load();
  if (found_out) *found_out = found;
  return found ? sh.winner.load() : 0;
}

// Single trial value — used by the Python wrapper's self-test.
uint64_t tpu_bm_pow_trial(const uint8_t* initial_hash, uint64_t nonce) {
  u64 ih[8];
  for (int i = 0; i < 8; ++i) {
    u64 w = 0;
    for (int j = 0; j < 8; ++j) w = (w << 8) | initial_hash[i * 8 + j];
    ih[i] = w;
  }
  return trial(nonce, ih);
}

}  // extern "C"
