// redisson_tpu native runtime — C ABI shared library.
//
// TPU-native counterpart of the reference's two external native components
// (see SURVEY.md §2 header): the openhft zero-allocation hash intrinsics
// (/root/reference src: RedissonBloomFilter.java:117-118, misc/Hash.java:30-31)
// and the Netty epoll transport codec path (client/handler/CommandEncoder.java,
// client/handler/CommandDecoder.java). Here they become:
//
//   * batch MurmurHash3 x64 128 / xxHash64 over variable-length host keys —
//     the host ingest path that turns raw byte keys into u64 lanes before a
//     single fixed-shape device dispatch (hash-on-host, scatter-on-TPU);
//   * CRC16 (Redis key-slot polynomial, connection/CRC16.java) with hashtag
//     extraction (cluster/ClusterConnectionManager.java:543-558 semantics);
//   * a RESP2 wire codec: pipeline encoder + incremental streaming parser
//     (the durability/interop client's hot path).
//
// Everything is plain C ABI for ctypes; no Python headers needed.

#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <thread>
#include <vector>
#include <string>

#if defined(_WIN32)
#define RTPU_EXPORT extern "C" __declspec(dllexport)
#else
#define RTPU_EXPORT extern "C" __attribute__((visibility("default")))
#endif

// ---------------------------------------------------------------------------
// MurmurHash3 x64 128  (spec: smhasher MurmurHash3_x64_128)
// ---------------------------------------------------------------------------

static inline uint64_t rotl64(uint64_t x, int8_t r) {
  return (x << r) | (x >> (64 - r));
}

static inline uint64_t fmix64(uint64_t k) {
  k ^= k >> 33;
  k *= 0xff51afd7ed558ccdULL;
  k ^= k >> 33;
  k *= 0xc4ceb9fe1a85ec53ULL;
  k ^= k >> 33;
  return k;
}

static inline uint64_t load_le64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);  // little-endian hosts only (x86/arm64)
  return v;
}

static void murmur3_x64_128_one(const uint8_t* data, int64_t len, uint64_t seed,
                                uint64_t* out_h1, uint64_t* out_h2) {
  const uint64_t c1 = 0x87c37b91114253d5ULL;
  const uint64_t c2 = 0x4cf5ad432745937fULL;
  uint64_t h1 = seed, h2 = seed;
  const int64_t nblocks = len / 16;

  for (int64_t i = 0; i < nblocks; i++) {
    uint64_t k1 = load_le64(data + i * 16);
    uint64_t k2 = load_le64(data + i * 16 + 8);
    k1 *= c1; k1 = rotl64(k1, 31); k1 *= c2; h1 ^= k1;
    h1 = rotl64(h1, 27); h1 += h2; h1 = h1 * 5 + 0x52dce729ULL;
    k2 *= c2; k2 = rotl64(k2, 33); k2 *= c1; h2 ^= k2;
    h2 = rotl64(h2, 31); h2 += h1; h2 = h2 * 5 + 0x38495ab5ULL;
  }

  const uint8_t* tail = data + nblocks * 16;
  uint64_t k1 = 0, k2 = 0;
  switch (len & 15) {
    case 15: k2 ^= (uint64_t)tail[14] << 48; [[fallthrough]];
    case 14: k2 ^= (uint64_t)tail[13] << 40; [[fallthrough]];
    case 13: k2 ^= (uint64_t)tail[12] << 32; [[fallthrough]];
    case 12: k2 ^= (uint64_t)tail[11] << 24; [[fallthrough]];
    case 11: k2 ^= (uint64_t)tail[10] << 16; [[fallthrough]];
    case 10: k2 ^= (uint64_t)tail[9] << 8; [[fallthrough]];
    case 9:  k2 ^= (uint64_t)tail[8];
             k2 *= c2; k2 = rotl64(k2, 33); k2 *= c1; h2 ^= k2; [[fallthrough]];
    case 8:  k1 ^= (uint64_t)tail[7] << 56; [[fallthrough]];
    case 7:  k1 ^= (uint64_t)tail[6] << 48; [[fallthrough]];
    case 6:  k1 ^= (uint64_t)tail[5] << 40; [[fallthrough]];
    case 5:  k1 ^= (uint64_t)tail[4] << 32; [[fallthrough]];
    case 4:  k1 ^= (uint64_t)tail[3] << 24; [[fallthrough]];
    case 3:  k1 ^= (uint64_t)tail[2] << 16; [[fallthrough]];
    case 2:  k1 ^= (uint64_t)tail[1] << 8; [[fallthrough]];
    case 1:  k1 ^= (uint64_t)tail[0];
             k1 *= c1; k1 = rotl64(k1, 31); k1 *= c2; h1 ^= k1;
  }

  h1 ^= (uint64_t)len; h2 ^= (uint64_t)len;
  h1 += h2; h2 += h1;
  h1 = fmix64(h1); h2 = fmix64(h2);
  h1 += h2; h2 += h1;
  *out_h1 = h1; *out_h2 = h2;
}

// Batch over n variable-length keys stored concatenated in `data`;
// offsets[n+1] delimits key i as data[offsets[i]:offsets[i+1]].
RTPU_EXPORT void rtpu_murmur3_x64_128_batch(const uint8_t* data,
                                            const int64_t* offsets, int64_t n,
                                            uint64_t seed, uint64_t* out_h1,
                                            uint64_t* out_h2) {
  for (int64_t i = 0; i < n; i++) {
    murmur3_x64_128_one(data + offsets[i], offsets[i + 1] - offsets[i], seed,
                        out_h1 + i, out_h2 + i);
  }
}

// ---------------------------------------------------------------------------
// xxHash64  (spec: xxhash.com XXH64)
// ---------------------------------------------------------------------------

static const uint64_t XXP1 = 0x9E3779B185EBCA87ULL;
static const uint64_t XXP2 = 0xC2B2AE3D27D4EB4FULL;
static const uint64_t XXP3 = 0x165667B19E3779F9ULL;
static const uint64_t XXP4 = 0x85EBCA77C2B2AE63ULL;
static const uint64_t XXP5 = 0x27D4EB2F165667C5ULL;

static inline uint64_t xx_round(uint64_t acc, uint64_t lane) {
  acc += lane * XXP2;
  acc = rotl64(acc, 31);
  return acc * XXP1;
}

static uint64_t xxhash64_one(const uint8_t* p, int64_t len, uint64_t seed) {
  const uint8_t* end = p + len;
  uint64_t h;
  if (len >= 32) {
    uint64_t v1 = seed + XXP1 + XXP2, v2 = seed + XXP2, v3 = seed,
             v4 = seed - XXP1;
    const uint8_t* limit = end - 32;
    do {
      v1 = xx_round(v1, load_le64(p)); p += 8;
      v2 = xx_round(v2, load_le64(p)); p += 8;
      v3 = xx_round(v3, load_le64(p)); p += 8;
      v4 = xx_round(v4, load_le64(p)); p += 8;
    } while (p <= limit);
    h = rotl64(v1, 1) + rotl64(v2, 7) + rotl64(v3, 12) + rotl64(v4, 18);
    h = (h ^ xx_round(0, v1)) * XXP1 + XXP4;
    h = (h ^ xx_round(0, v2)) * XXP1 + XXP4;
    h = (h ^ xx_round(0, v3)) * XXP1 + XXP4;
    h = (h ^ xx_round(0, v4)) * XXP1 + XXP4;
  } else {
    h = seed + XXP5;
  }
  h += (uint64_t)len;
  while (p + 8 <= end) {
    h ^= xx_round(0, load_le64(p));
    h = rotl64(h, 27) * XXP1 + XXP4;
    p += 8;
  }
  if (p + 4 <= end) {
    uint32_t v;
    std::memcpy(&v, p, 4);
    h ^= (uint64_t)v * XXP1;
    h = rotl64(h, 23) * XXP2 + XXP3;
    p += 4;
  }
  while (p < end) {
    h ^= (uint64_t)(*p) * XXP5;
    h = rotl64(h, 11) * XXP1;
    p++;
  }
  h ^= h >> 33; h *= XXP2; h ^= h >> 29; h *= XXP3; h ^= h >> 32;
  return h;
}

RTPU_EXPORT void rtpu_xxhash64_batch(const uint8_t* data,
                                     const int64_t* offsets, int64_t n,
                                     uint64_t seed, uint64_t* out) {
  for (int64_t i = 0; i < n; i++) {
    out[i] = xxhash64_one(data + offsets[i], offsets[i + 1] - offsets[i], seed);
  }
}

// ---------------------------------------------------------------------------
// CRC16 — Redis key-slot polynomial (CCITT, poly 0x1021), lookup table.
// Matches /root/reference connection/CRC16.java.
// ---------------------------------------------------------------------------

struct Crc16Table {
  uint16_t tab[256];
  Crc16Table() {
    for (int i = 0; i < 256; i++) {
      uint16_t crc = (uint16_t)(i << 8);
      for (int j = 0; j < 8; j++)
        crc = (crc & 0x8000) ? (uint16_t)((crc << 1) ^ 0x1021)
                             : (uint16_t)(crc << 1);
      tab[i] = crc;
    }
  }
};
static const Crc16Table crc16_table;  // built at load time: no init race
static const uint16_t* const crc16_tab = crc16_table.tab;

static uint16_t crc16_one(const uint8_t* p, int64_t len) {
  uint16_t crc = 0;
  for (int64_t i = 0; i < len; i++)
    crc = (uint16_t)((crc << 8) ^ crc16_tab[((crc >> 8) ^ p[i]) & 0xFF]);
  return crc;
}

RTPU_EXPORT uint16_t rtpu_crc16(const uint8_t* p, int64_t len) {
  return crc16_one(p, len);
}

// Slot calc with {hashtag} extraction: if the key contains a non-empty
// brace-delimited section, only that section is hashed (Redis cluster rule).
RTPU_EXPORT void rtpu_keyslot_batch(const uint8_t* data, const int64_t* offsets,
                                    int64_t n, int32_t* out_slots) {
  for (int64_t i = 0; i < n; i++) {
    const uint8_t* p = data + offsets[i];
    int64_t len = offsets[i + 1] - offsets[i];
    int64_t start = -1;
    for (int64_t j = 0; j < len; j++) {
      if (p[j] == '{') { start = j + 1; break; }
    }
    if (start >= 0) {
      for (int64_t j = start; j < len; j++) {
        if (p[j] == '}') {
          if (j > start) { p += start; len = j - start; }
          break;
        }
      }
    }
    out_slots[i] = crc16_one(p, len) & 16383;
  }
}

// ---------------------------------------------------------------------------
// RESP2 pipeline encoder.
//
// Input: nargs byte strings (concatenated + offsets) per command, ncmds
// commands delimited by cmd_arg_counts. Output: a single malloc'd buffer the
// caller frees with rtpu_free. Layout mirrors the reference CommandEncoder
// (*N\r\n then $len\r\n<arg>\r\n per arg) and CommandBatchEncoder
// (concatenation).
// ---------------------------------------------------------------------------

RTPU_EXPORT void rtpu_free(void* p) { std::free(p); }

RTPU_EXPORT uint8_t* rtpu_resp_encode_pipeline(const uint8_t* args,
                                               const int64_t* offsets,
                                               const int32_t* cmd_arg_counts,
                                               int64_t ncmds,
                                               int64_t* out_len) {
  std::string out;
  out.reserve(256 * (size_t)ncmds);
  char head[32];
  int64_t a = 0;
  for (int64_t c = 0; c < ncmds; c++) {
    int n = std::snprintf(head, sizeof(head), "*%d\r\n", cmd_arg_counts[c]);
    out.append(head, n);
    for (int32_t k = 0; k < cmd_arg_counts[c]; k++, a++) {
      int64_t len = offsets[a + 1] - offsets[a];
      n = std::snprintf(head, sizeof(head), "$%lld\r\n", (long long)len);
      out.append(head, n);
      out.append((const char*)(args + offsets[a]), (size_t)len);
      out.append("\r\n", 2);
    }
  }
  uint8_t* buf = (uint8_t*)std::malloc(out.size() ? out.size() : 1);
  std::memcpy(buf, out.data(), out.size());
  *out_len = (int64_t)out.size();
  return buf;
}

// ---------------------------------------------------------------------------
// RESP2 incremental parser.
//
// Streaming, reentrant across partial reads — the C++ analogue of the
// reference's ReplayingDecoder checkpoint machine
// (client/handler/CommandDecoder.java State/StateLevel). Completed replies
// are serialized into a flat little-endian stream Python unpacks:
//   [u8 type][i64 payload]
//     type '+' / '-' / '$': payload = byte length, followed by the bytes
//                           ($ with length -1 = null bulk, no bytes)
//     type ':'            : payload = integer value, no bytes
//     type '*'            : payload = element count (-1 = null array);
//                           elements follow recursively, pre-order
// ---------------------------------------------------------------------------

struct RespParser {
  std::string buf;      // unconsumed wire bytes
  size_t pos = 0;       // parse cursor into buf
  std::string out;      // flattened completed replies
  int64_t nready = 0;   // completed top-level replies in `out`
  bool poisoned = false;  // unrecoverable protocol violation seen
};

RTPU_EXPORT RespParser* rtpu_resp_parser_new() { return new RespParser(); }
RTPU_EXPORT void rtpu_resp_parser_free(RespParser* p) { delete p; }

static void emit_header(std::string& out, uint8_t type, int64_t payload) {
  out.push_back((char)type);
  out.append((const char*)&payload, 8);
}

// RESP arrays nest one C-stack frame per level; real replies nest a
// handful deep. Cap to keep hostile/corrupt streams from overflowing the
// stack (the error path below tears the stream down).
static const int kMaxRespDepth = 64;

// strtoll of a header line that is not NUL-terminated in the buffer.
static int64_t line_int(const char* s, size_t n) {
  char small[32];
  if (n < sizeof(small)) {
    std::memcpy(small, s, n);
    small[n] = 0;
    return std::strtoll(small, nullptr, 10);
  }
  return std::strtoll(std::string(s, n).c_str(), nullptr, 10);
}

// Try to parse one reply at `pos`; append flattened form to `out`.
// Returns true and advances pos past the reply on success; false (pos
// untouched, out possibly partially longer — caller rolls back) if the
// buffer holds only a prefix.
static bool parse_one(RespParser* p, size_t& pos, std::string& out,
                      int depth = 0) {
  const std::string& b = p->buf;
  if (pos >= b.size()) return false;
  char t = b[pos];
  size_t eol = b.find("\r\n", pos + 1);
  if (eol == std::string::npos) return false;
  const char* line = b.data() + pos + 1;
  size_t line_len = eol - pos - 1;
  size_t after = eol + 2;
  switch (t) {
    case '+': case '-': {
      emit_header(out, (uint8_t)t, (int64_t)line_len);
      out.append(line, line_len);
      pos = after;
      return true;
    }
    case ':': {
      emit_header(out, ':', line_int(line, line_len));
      pos = after;
      return true;
    }
    case '$': {
      int64_t len = line_int(line, line_len);
      if (len < 0) {  // null bulk
        emit_header(out, '$', -1);
        pos = after;
        return true;
      }
      if (b.size() < after + (size_t)len + 2) return false;
      emit_header(out, '$', len);
      out.append(b, after, (size_t)len);
      pos = after + (size_t)len + 2;
      return true;
    }
    case '*': {
      if (depth >= kMaxRespDepth) {
        // Unrecoverable: request/response framing is lost. Poison the
        // parser; feed() surfaces one top-level error reply and the
        // client tears the connection down.
        p->poisoned = true;
        return false;
      }
      int64_t count = line_int(line, line_len);
      emit_header(out, '*', count);
      pos = after;
      for (int64_t i = 0; i < count; i++) {
        if (!parse_one(p, pos, out, depth + 1)) return false;
      }
      return true;
    }
    default:
      // Protocol violation: framing is lost for good — poison.
      p->poisoned = true;
      return false;
  }
}

// Feed wire bytes; returns the number of COMPLETE top-level replies now
// buffered (cumulative, decremented by take).
RTPU_EXPORT int64_t rtpu_resp_parser_feed(RespParser* p, const uint8_t* data,
                                          int64_t len) {
  if (p->poisoned) {
    // One error reply was already surfaced; drop everything after it.
    return p->nready;
  }
  p->buf.append((const char*)data, (size_t)len);
  for (;;) {
    size_t pos = p->pos;
    size_t mark = p->out.size();
    if (!parse_one(p, pos, p->out)) {
      p->out.resize(mark);  // drop a partial reply's flattened prefix
      break;
    }
    p->pos = pos;
    p->nready++;
  }
  if (p->poisoned) {
    static const char kMsg[] = "ERR protocol violation (bad header or nesting)";
    emit_header(p->out, '-', (int64_t)(sizeof(kMsg) - 1));
    p->out.append(kMsg, sizeof(kMsg) - 1);
    p->nready++;
    p->buf.clear();
    p->pos = 0;
    return p->nready;
  }
  // Compact consumed prefix occasionally to bound memory.
  if (p->pos > (1u << 16) && p->pos * 2 > p->buf.size()) {
    p->buf.erase(0, p->pos);
    p->pos = 0;
  }
  return p->nready;
}

// Size of the pending flattened-reply stream (bytes).
RTPU_EXPORT int64_t rtpu_resp_parser_pending(RespParser* p) {
  return (int64_t)p->out.size();
}

// Copy out the flattened stream of all completed replies and reset it.
// Returns bytes written; caller sizes the buffer via _pending first.
RTPU_EXPORT int64_t rtpu_resp_parser_take(RespParser* p, uint8_t* dst,
                                          int64_t cap) {
  int64_t n = (int64_t)p->out.size();
  if (n > cap) return -1;
  std::memcpy(dst, p->out.data(), (size_t)n);
  p->out.clear();
  p->nready = 0;
  return n;
}

static inline int64_t flat_payload(const std::string& s, size_t pos) {
  int64_t v;
  std::memcpy(&v, s.data() + pos + 1, 8);
  return v;
}

// End of the flattened item that starts at `pos`.
static size_t flat_skip(const std::string& s, size_t pos) {
  int64_t remaining = 1;
  while (remaining-- > 0) {
    char t = s[pos];
    int64_t payload = flat_payload(s, pos);
    pos += 9;
    if (t == '*') {
      if (payload > 0) remaining += payload;
    } else if (t != ':' && payload > 0) {
      pos += (size_t)payload;
    }
  }
  return pos;
}

// Request-direction take: the completed top-level frames as an index, so a
// server reads a command's arguments without one object per argument.
//   frames [3 * nready]: per frame (nargs, first_arg, 0) when the frame is
//     an array of non-null bulk or simple strings, whose bodies are copied
//     back to back into `bodies` and described by args_off / args_len rows
//     first_arg .. first_arg + nargs - 1; else (-1, start, end): the
//     frame's flattened form (the take() stream) at bodies[start, end).
// Returns the number of argument rows, or -1 when a capacity is short
// (nothing is consumed then). Resets the stream like take().
RTPU_EXPORT int64_t rtpu_resp_parser_take_requests(
    RespParser* p, uint8_t* bodies, int64_t bodies_cap, int64_t* frames,
    int64_t frames_cap, int64_t* args_off, int64_t* args_len,
    int64_t args_cap) {
  const std::string& s = p->out;
  if (3 * p->nready > frames_cap) return -1;
  size_t pos = 0;
  int64_t nb = 0, na = 0;
  for (int64_t f = 0; f < p->nready; f++) {
    bool indexed = s[pos] == '*' && flat_payload(s, pos) >= 0;
    int64_t count = indexed ? flat_payload(s, pos) : 0;
    size_t q = pos + 9;
    for (int64_t k = 0; indexed && k < count; k++) {
      char t = s[q];
      int64_t len = flat_payload(s, q);
      indexed = (t == '$' || t == '+') && len >= 0;
      q += 9 + (size_t)(indexed ? len : 0);
    }
    if (indexed) {
      if (na + count > args_cap) return -1;
      frames[3 * f] = count;
      frames[3 * f + 1] = na;
      frames[3 * f + 2] = 0;
      q = pos + 9;
      for (int64_t k = 0; k < count; k++, na++) {
        int64_t len = flat_payload(s, q);
        if (nb + len > bodies_cap) return -1;
        std::memcpy(bodies + nb, s.data() + q + 9, (size_t)len);
        args_off[na] = nb;
        args_len[na] = len;
        nb += len;
        q += 9 + (size_t)len;
      }
      pos = q;
    } else {
      size_t end = flat_skip(s, pos);
      int64_t len = (int64_t)(end - pos);
      if (nb + len > bodies_cap) return -1;
      std::memcpy(bodies + nb, s.data() + pos, (size_t)len);
      frames[3 * f] = -1;
      frames[3 * f + 1] = nb;
      frames[3 * f + 2] = nb + len;
      nb += len;
      pos = end;
    }
  }
  p->out.clear();
  p->nready = 0;
  return na;
}

// ---------------------------------------------------------------------------
// Host-side HLL fold: hash keys and fold bucket-max ranks into 16384
// uint8 registers in one pass. Used by the durability path and as a CPU
// fallback engine; the TPU path does the same fold on-device.
// p=14 geometry matches ops/hll.py (Redis default, antirez HLL).
// ---------------------------------------------------------------------------

RTPU_EXPORT void rtpu_hll_fold_batch(const uint8_t* data,
                                     const int64_t* offsets, int64_t n,
                                     uint64_t seed, uint8_t* regs /*16384*/) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h1, h2;
    murmur3_x64_128_one(data + offsets[i], offsets[i + 1] - offsets[i], seed,
                        &h1, &h2);
    uint32_t bucket = (uint32_t)(h1 & 16383);
    uint64_t rest = h1 >> 14;
    // rank = leading-zero count of the remaining 50 bits + 1, capped.
    int rank = 1;
    while (rank <= 50 && !(rest & 1)) { rest >>= 1; rank++; }
    if ((uint8_t)rank > regs[bucket]) regs[bucket] = (uint8_t)rank;
  }
}

// Specialized murmur3_x64_128 h1 for one u64 key hashed as its 8-byte LE
// encoding: the whole key is the tail (no body blocks), so the canonical
// algorithm collapses to one k1 mix + finalization. Must stay bit-identical
// to ops/hashing.py::murmur3_x64_128_u64 (golden-tested both ways).
static inline uint64_t mm3_h1_u64(uint64_t key, uint64_t seed) {
  const uint64_t c1 = 0x87c37b91114253d5ULL;
  const uint64_t c2 = 0x4cf5ad432745937fULL;
  uint64_t k1 = key;
  k1 *= c1; k1 = rotl64(k1, 31); k1 *= c2;
  uint64_t h1 = seed ^ k1;
  uint64_t h2 = seed;
  h1 ^= 8; h2 ^= 8;
  h1 += h2;
  h2 += h1;
  h1 = fmix64(h1);
  h2 = fmix64(h2);
  return h1 + h2;
}

// One u64 key folded into a register array — THE p=14 fold step (rank =
// ctz((h1 >> 14) | 2^50) + 1, range [1, 51]; ops/hll.py bucket_rank /
// Redis hllPatLen). Shared by the flat and bank folds so the formula can
// never diverge between them.
static inline void hll_fold_step_u64(uint64_t key, uint64_t seed,
                                     uint8_t* regs) {
  uint64_t h1 = mm3_h1_u64(key, seed);
  uint32_t bucket = (uint32_t)(h1 & 16383u);
  uint64_t rest = (h1 >> 14) | (1ULL << 50);
  uint8_t rank = (uint8_t)(__builtin_ctzll(rest) + 1);
  if (rank > regs[bucket]) regs[bucket] = rank;
}

static void hll_fold_u64_range(const uint64_t* keys, int64_t n, uint64_t seed,
                               uint8_t* regs) {
  for (int64_t i = 0; i < n; i++) hll_fold_step_u64(keys[i], seed, regs);
}

// Host-side HLL fold over u64 keys: the transfer-adaptive ingest path.
// When the host->device link is slow, shipping
// 8 B/key loses to folding locally and shipping the 16 KB sketch — the
// same move-the-reduction-across-the-slow-link trick as PFMERGE across
// ICI. Threads fold disjoint slices into private register arrays, merged
// by elementwise max (HLL folds are commutative).
RTPU_EXPORT void rtpu_hll_fold_u64(const uint64_t* keys, int64_t n,
                                   uint64_t seed, uint8_t* regs /*16384*/,
                                   int32_t nthreads) {
  const int64_t kMinPerThread = 1 << 16;
  if (nthreads > 16) nthreads = 16;
  if (nthreads > (int32_t)(n / kMinPerThread))
    nthreads = (int32_t)(n / kMinPerThread);
  if (nthreads <= 1) {
    hll_fold_u64_range(keys, n, seed, regs);
    return;
  }
  std::vector<std::vector<uint8_t>> scratch(
      (size_t)(nthreads - 1), std::vector<uint8_t>(16384, 0));
  std::vector<std::thread> threads;
  int64_t per = n / nthreads;
  for (int32_t t = 1; t < nthreads; t++) {
    int64_t s = per * t;
    int64_t e = (t == nthreads - 1) ? n : per * (t + 1);
    threads.emplace_back([keys, s, e, seed, &scratch, t] {
      hll_fold_u64_range(keys + s, e - s, seed, scratch[(size_t)t - 1].data());
    });
  }
  hll_fold_u64_range(keys, per, seed, regs);
  for (auto& th : threads) th.join();
  for (auto& sc : scratch)
    for (int i = 0; i < 16384; i++)
      if (sc[(size_t)i] > regs[i]) regs[i] = sc[(size_t)i];
}

// Row-aware u64 fold into a BANK of sketches (bank = nrows x 16384 uint8,
// row-major): the host half of the sharded-bank streaming ingest — fold a
// keyed stream into a host bank mirror, ship/absorb the bank periodically
// instead of 8 B/key (BASELINE config 4's host path).
RTPU_EXPORT void rtpu_hll_fold_u64_rows(const uint64_t* keys,
                                        const int32_t* rows, int64_t n,
                                        uint64_t seed, uint8_t* bank,
                                        int64_t nrows) {
  for (int64_t i = 0; i < n; i++) {
    int64_t row = rows[i];
    if (row < 0 || row >= nrows) continue;  // defensive: never scribble
    hll_fold_step_u64(keys[i], seed, bank + row * 16384);
  }
}

// Row-layout byte-key fold: keys arrive as the executor's padded [n, w]
// uint8 matrix + per-key lengths (no re-concatenation needed on the
// dispatcher). Same register semantics as rtpu_hll_fold_u64.
RTPU_EXPORT void rtpu_hll_fold_rows(const uint8_t* data, int64_t w,
                                    const int32_t* lengths, int64_t n,
                                    uint64_t seed, uint8_t* regs /*16384*/) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h1, h2;
    murmur3_x64_128_one(data + i * w, lengths[i], seed, &h1, &h2);
    uint32_t bucket = (uint32_t)(h1 & 16383u);
    uint64_t rest = (h1 >> 14) | (1ULL << 50);
    uint8_t rank = (uint8_t)(__builtin_ctzll(rest) + 1);
    if (rank > regs[bucket]) regs[bucket] = rank;
  }
}

// ---------------------------------------------------------------------------
// Host-side Bloom fold/probe: the transfer-adaptive ingest path for the
// Bloom tier (same move-the-reduction trick as rtpu_hll_fold_u64 — on a
// slow host->device link, fold membership bits locally and ship/OR the
// bitmap once instead of 8 B/key + per-key bools).
//
// Index semantics are identical to ops/bloom.py indexes(): hash the key
// with MurmurHash3 x64 128 (u64 keys as their 8-byte LE encoding), then
// walk idx_i = ((h1 + i*h2) mod 2^64) mod m — the uint64 accumulator wraps
// naturally. Bit layout is numpy packbits big-endian (absolute bit i ->
// byte i>>3, bit 7-(i&7)) so host mirrors interoperate with np.packbits /
// np.unpackbits and the durability blobs.
// ---------------------------------------------------------------------------

static inline void mm3_u64_pair(uint64_t key, uint64_t seed, uint64_t* o1,
                                uint64_t* o2) {
  // x86-64 is little-endian: the in-memory bytes of `key` ARE its 8-byte
  // LE encoding (the encoding murmur3_x64_128_u64 hashes on device).
  murmur3_x64_128_one(reinterpret_cast<const uint8_t*>(&key), 8, seed, o1, o2);
}

static inline int bloom_get_bit(const uint8_t* bits, uint64_t idx) {
  return (bits[idx >> 3] >> (7 - (idx & 7))) & 1;
}

// Threads share the bitmap; byte-granular |= is a read-modify-write, so a
// plain store could drop a concurrent thread's bit in the same byte —
// atomic OR keeps every set (relaxed order: bloom bits are monotone).
static inline void bloom_set_bit_atomic(uint8_t* bits, uint64_t idx) {
  __atomic_fetch_or(&bits[idx >> 3], (uint8_t)(0x80u >> (idx & 7u)),
                    __ATOMIC_RELAXED);
}

template <bool Atomic>
static inline uint8_t bloom_fold_one(uint64_t h1, uint64_t h2, int32_t k,
                                     uint64_t m, uint8_t* bits) {
  uint64_t acc = h1;
  uint8_t fresh = 0;
  for (int32_t i = 0; i < k; i++) {
    uint64_t idx = acc % m;
    if (!bloom_get_bit(bits, idx)) {
      fresh = 1;
      if (Atomic)  // lock-prefixed RMW only when threads share the bitmap
        bloom_set_bit_atomic(bits, idx);
      else
        bits[idx >> 3] |= (uint8_t)(0x80u >> (idx & 7u));
    }
    acc += h2;
  }
  return fresh;
}

static inline uint8_t bloom_probe_one(uint64_t h1, uint64_t h2, int32_t k,
                                      uint64_t m, const uint8_t* bits) {
  uint64_t acc = h1;
  for (int32_t i = 0; i < k; i++) {
    if (!bloom_get_bit(bits, acc % m)) return 0;  // early out: most
    acc += h2;                                    // negatives fail bit 0
  }
  return 1;
}

template <bool Atomic>
static void bloom_fold_u64_range(const uint64_t* keys, int64_t n,
                                 uint64_t seed, int32_t k, uint64_t m,
                                 uint8_t* bits, uint8_t* newly) {
  // The walk is memory-latency-bound (k random bytes in an L3-sized
  // bitmap): stage a block of keys' indexes, software-prefetch them all,
  // then apply — overlapping the misses instead of serializing them.
  constexpr int64_t kBlock = 32;
  constexpr int32_t kMaxK = 32;
  uint64_t idx[kBlock * kMaxK];
  int32_t kk = k > kMaxK ? kMaxK : k;
  int64_t i = 0;
  for (; i + kBlock <= n && k <= kMaxK; i += kBlock) {
    for (int64_t b = 0; b < kBlock; b++) {
      uint64_t h1, h2;
      mm3_u64_pair(keys[i + b], seed, &h1, &h2);
      uint64_t acc = h1;
      for (int32_t j = 0; j < kk; j++) {
        uint64_t ix = acc % m;
        idx[b * kk + j] = ix;
        __builtin_prefetch(&bits[ix >> 3], 1, 1);
        acc += h2;
      }
    }
    for (int64_t b = 0; b < kBlock; b++) {
      uint8_t fresh = 0;
      for (int32_t j = 0; j < kk; j++) {
        uint64_t ix = idx[b * kk + j];
        if (!bloom_get_bit(bits, ix)) {
          fresh = 1;
          if (Atomic)
            bloom_set_bit_atomic(bits, ix);
          else
            bits[ix >> 3] |= (uint8_t)(0x80u >> (ix & 7u));
        }
      }
      if (newly) newly[i + b] = fresh;
    }
  }
  for (; i < n; i++) {
    uint64_t h1, h2;
    mm3_u64_pair(keys[i], seed, &h1, &h2);
    uint8_t fresh = bloom_fold_one<Atomic>(h1, h2, k, m, bits);
    if (newly) newly[i] = fresh;
  }
}

// Fold a u64 key batch into a shared packed bitmap. `newly` (optional,
// size n) gets 1 where the key set at least one previously-unset bit.
// The "previously" read races across threads only for two keys sharing a
// bit in the same batch — the same looseness the device path's per-chunk
// evaluation already documents (executor batch-visibility contract).
RTPU_EXPORT void rtpu_bloom_fold_u64(const uint64_t* keys, int64_t n,
                                     uint64_t seed, int32_t k, uint64_t m,
                                     uint8_t* bits, uint8_t* newly,
                                     int32_t nthreads) {
  const int64_t kMinPerThread = 1 << 15;
  if (nthreads > 16) nthreads = 16;
  if (nthreads > (int32_t)(n / kMinPerThread))
    nthreads = (int32_t)(n / kMinPerThread);
  if (nthreads <= 1) {
    bloom_fold_u64_range<false>(keys, n, seed, k, m, bits, newly);
    return;
  }
  std::vector<std::thread> threads;
  int64_t per = n / nthreads;
  for (int32_t t = 1; t < nthreads; t++) {
    int64_t s = per * t;
    int64_t e = (t == nthreads - 1) ? n : per * (t + 1);
    threads.emplace_back([=] {
      bloom_fold_u64_range<true>(keys + s, e - s, seed, k, m, bits,
                                 newly ? newly + s : nullptr);
    });
  }
  bloom_fold_u64_range<true>(keys, per, seed, k, m, bits, newly);
  for (auto& th : threads) th.join();
}

static void bloom_probe_u64_range(const uint64_t* keys, int64_t n,
                                  uint64_t seed, int32_t k, uint64_t m,
                                  const uint8_t* bits, uint8_t* out) {
  // Same staged-prefetch structure as the fold: prefetch only each key's
  // FIRST index (negative probes usually fail there; positive probes pay
  // the remaining misses, still overlapped across the block).
  constexpr int64_t kBlock = 32;
  uint64_t h1s[kBlock], h2s[kBlock];
  int64_t i = 0;
  for (; i + kBlock <= n; i += kBlock) {
    for (int64_t b = 0; b < kBlock; b++) {
      mm3_u64_pair(keys[i + b], seed, &h1s[b], &h2s[b]);
      __builtin_prefetch(&bits[(h1s[b] % m) >> 3], 0, 1);
    }
    for (int64_t b = 0; b < kBlock; b++)
      out[i + b] = bloom_probe_one(h1s[b], h2s[b], k, m, bits);
  }
  for (; i < n; i++) {
    uint64_t h1, h2;
    mm3_u64_pair(keys[i], seed, &h1, &h2);
    out[i] = bloom_probe_one(h1, h2, k, m, bits);
  }
}

// Membership probe of a u64 key batch against a packed bitmap (read-only,
// embarrassingly parallel).
RTPU_EXPORT void rtpu_bloom_contains_u64(const uint64_t* keys, int64_t n,
                                         uint64_t seed, int32_t k, uint64_t m,
                                         const uint8_t* bits, uint8_t* out,
                                         int32_t nthreads) {
  const int64_t kMinPerThread = 1 << 15;
  if (nthreads > 16) nthreads = 16;
  if (nthreads > (int32_t)(n / kMinPerThread))
    nthreads = (int32_t)(n / kMinPerThread);
  if (nthreads <= 1) {
    bloom_probe_u64_range(keys, n, seed, k, m, bits, out);
    return;
  }
  std::vector<std::thread> threads;
  int64_t per = n / nthreads;
  for (int32_t t = 1; t < nthreads; t++) {
    int64_t s = per * t;
    int64_t e = (t == nthreads - 1) ? n : per * (t + 1);
    threads.emplace_back([=] {
      bloom_probe_u64_range(keys + s, e - s, seed, k, m, bits, out + s);
    });
  }
  bloom_probe_u64_range(keys, per, seed, k, m, bits, out);
  for (auto& th : threads) th.join();
}

// Row-layout byte-key variants (the executor's padded [n, w] matrix +
// per-key lengths, like rtpu_hll_fold_rows).
RTPU_EXPORT void rtpu_bloom_fold_rows(const uint8_t* data, int64_t w,
                                      const int32_t* lengths, int64_t n,
                                      uint64_t seed, int32_t k, uint64_t m,
                                      uint8_t* bits, uint8_t* newly) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h1, h2;
    murmur3_x64_128_one(data + i * w, lengths[i], seed, &h1, &h2);
    uint8_t fresh = bloom_fold_one<false>(h1, h2, k, m, bits);
    if (newly) newly[i] = fresh;
  }
}

RTPU_EXPORT void rtpu_bloom_contains_rows(const uint8_t* data, int64_t w,
                                          const int32_t* lengths, int64_t n,
                                          uint64_t seed, int32_t k, uint64_t m,
                                          const uint8_t* bits, uint8_t* out) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h1, h2;
    murmur3_x64_128_one(data + i * w, lengths[i], seed, &h1, &h2);
    out[i] = bloom_probe_one(h1, h2, k, m, bits);
  }
}

// Population count of a packed bitmap (host-side BITCOUNT for the mirror).
RTPU_EXPORT uint64_t rtpu_popcount(const uint8_t* bits, int64_t nbytes) {
  uint64_t total = 0;
  int64_t i = 0;
  for (; i + 8 <= nbytes; i += 8) {
    uint64_t w;
    std::memcpy(&w, bits + i, 8);
    total += (uint64_t)__builtin_popcountll(w);
  }
  for (; i < nbytes; i++) total += (uint64_t)__builtin_popcount(bits[i]);
  return total;
}

RTPU_EXPORT const char* rtpu_version() { return "redisson-tpu-native 1.0"; }
