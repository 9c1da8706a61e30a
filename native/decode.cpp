// Native host-side video decoder: the TPU-native stand-in for the
// reference's NVVL fork (SURVEY.md §2.2 N2; reference usage at
// models/r2p1d/model.py:123-145).  TPUs have no video ASIC, so decode
// is host CPU work; this library makes it native C++ with a worker
// pool so the decode stage keeps up with the accelerator.
//
// Formats:
//  * Uncompressed YUV4MPEG2 (.y4m), 4:2:0 or 4:4:4 — the format the
//    pure-numpy Y4MDecoder (rnb_tpu/decode/__init__.py) also speaks;
//    the two backends are numerically parity-tested against each
//    other.
//  * MJPEG (.mjpg): concatenated baseline JPEG frames, decoded by the
//    self-contained baseline decoder below (Huffman + dequant + IDCT,
//    4:2:0 or 4:4:4) — REAL codec compute in the measured loop, the
//    role NVDEC played for the reference (README.md:42-110). Parity
//    oracle: PIL/libjpeg in tests/test_mjpeg.py.
// The container is sniffed from the magic bytes; every entry point
// accepts either.
//
// Design notes:
//  * The decode of one output pixel needs exactly one Y/U/V sample
//    (nearest-neighbour chroma upsample + box-resize are both pure
//    index maps), so decode, upsample, convert and resize are fused
//    into a single gather per output pixel — unlike the numpy path,
//    the full frame is never materialized.
//  * C ABI only (consumed via ctypes; pybind11 is not available in
//    this image).  All buffers are caller-owned.
//  * The pool is a plain mutex+condvar job queue; one ticket per
//    submitted decode, waitable from any thread.

#include <sys/stat.h>

#include <atomic>
#include <chrono>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

constexpr int kErrIo = -1;        // open/seek/read failure
constexpr int kErrFormat = -2;    // not a y4m / bad header / bad marker
constexpr int kErrColorspace = -3;
constexpr int kErrArg = -4;
constexpr int kErrBudget = -5;    // dct: spectrum exceeds the wire budget

struct Y4mMeta {
  int width = 0;
  int height = 0;
  int subsample = 1;           // 1 = 4:4:4, 2 = 4:2:0
  long long frame_bytes = 0;
  long long data_start = 0;    // offset of first FRAME marker
  long long marker_len = 0;    // length of b"FRAME...\n" incl newline
  long long stride = 0;        // marker + payload
  long long count = 0;         // number of frames
};

// Read one '\n'-terminated line starting at `off`.  Returns false on
// IO error or if no newline is found within `maxlen` bytes.
bool ReadLine(FILE* f, long long off, std::string* line,
              size_t maxlen = 65536) {
  if (fseeko(f, off, SEEK_SET) != 0) return false;
  line->clear();
  int c;
  while (line->size() < maxlen && (c = fgetc(f)) != EOF) {
    line->push_back(static_cast<char>(c));
    if (c == '\n') return true;
  }
  return false;
}

int ProbeFile(const char* path, Y4mMeta* meta) {
  FILE* f = fopen(path, "rb");
  if (!f) return kErrIo;
  std::string header;
  if (!ReadLine(f, 0, &header) || header.rfind("YUV4MPEG2", 0) != 0) {
    fclose(f);
    return kErrFormat;
  }
  meta->width = meta->height = 0;
  std::string cs = "420";
  // tokens after the magic, space-separated, tag = first char
  size_t pos = header.find(' ');
  while (pos != std::string::npos && pos + 1 < header.size()) {
    size_t end = header.find_first_of(" \n", pos + 1);
    std::string token = header.substr(pos + 1, end - pos - 1);
    if (!token.empty()) {
      char tag = token[0];
      std::string val = token.substr(1);
      if (tag == 'W') meta->width = atoi(val.c_str());
      else if (tag == 'H') meta->height = atoi(val.c_str());
      else if (tag == 'C') cs = val;
    }
    pos = (end == std::string::npos || header[end] == '\n')
              ? std::string::npos : end;
  }
  if (meta->width <= 0 || meta->height <= 0) {
    fclose(f);
    return kErrFormat;
  }
  const long long wh =
      static_cast<long long>(meta->width) * meta->height;
  if (cs.rfind("420", 0) == 0) {
    meta->subsample = 2;
    meta->frame_bytes = wh * 3 / 2;
  } else if (cs.rfind("444", 0) == 0) {
    meta->subsample = 1;
    meta->frame_bytes = wh * 3;
  } else {
    fclose(f);
    return kErrColorspace;
  }
  meta->data_start = static_cast<long long>(header.size());
  std::string marker;
  if (!ReadLine(f, meta->data_start, &marker) ||
      marker.rfind("FRAME", 0) != 0) {
    fclose(f);
    return kErrFormat;
  }
  meta->marker_len = static_cast<long long>(marker.size());
  meta->stride = meta->marker_len + meta->frame_bytes;
  if (fseeko(f, 0, SEEK_END) != 0) {
    fclose(f);
    return kErrIo;
  }
  const long long size = ftello(f);
  fclose(f);
  meta->count = (size - meta->data_start) / meta->stride;
  if (meta->count <= 0) return kErrFormat;
  return 0;
}

inline unsigned char ClipByte(float v) {
  if (v < 0.f) v = 0.f;
  if (v > 255.f) v = 255.f;
  return static_cast<unsigned char>(v);  // trunc, matches np.astype(u8)
}

// ---------------------------------------------------------------------------
// Baseline JPEG decoder (ITU T.81 sequential DCT, 8-bit, Huffman).
// Self-contained: no libjpeg in this image. Decodes one frame into
// planar YCbCr at the source geometry (the same payload layout the y4m
// path reads), so the fused convert/gather stages are shared between
// containers. Supports 3-component 4:2:0 (2x2,1x1,1x1) and 4:4:4
// (1x1 x3) sampling, restart markers, multiple DQT/DHT segments.

constexpr int kZigzag[64] = {
    0,  1,  8,  16, 9,  2,  3,  10, 17, 24, 32, 25, 18, 11, 4,  5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6,  7,  14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63};

struct HuffTable {
  // canonical decode per ITU T.81 F.2.2.3, plus an 8-bit lookahead
  // table (libjpeg's technique): one Peek(8) resolves the vast
  // majority of symbols without the per-bit walk.
  int mincode[17] = {0};
  int maxcode[17] = {0};  // -1 where no codes of that length
  int valptr[17] = {0};
  unsigned char values[256] = {0};
  unsigned short lut[256] = {0};  // (len << 8) | symbol; 0 = miss
  bool present = false;

  void Build(const unsigned char counts[16], const unsigned char* vals,
             int nvals) {
    for (int i = 0; i < nvals && i < 256; ++i) values[i] = vals[i];
    int code = 0, k = 0;
    std::memset(lut, 0, sizeof(lut));
    for (int l = 1; l <= 16; ++l) {
      valptr[l] = k;
      mincode[l] = code;
      const int n = counts[l - 1];
      if (l <= 8) {
        for (int i = 0; i < n; ++i) {
          const int c = code + i;
          const int base = c << (8 - l);
          for (int fill = 0; fill < (1 << (8 - l)); ++fill)
            lut[base | fill] =
                static_cast<unsigned short>((l << 8) | values[k + i]);
        }
      }
      code += n;
      k += n;
      maxcode[l] = n ? code - 1 : -1;
      code <<= 1;
    }
    present = true;
  }
};

struct BitReader {
  const unsigned char* d;
  size_t n, pos;
  unsigned long long acc = 0;  // MSB-justified within `count` bits
  int count = 0;
  bool starved = false;  // zero bits were synthesized past a marker/EOF

  BitReader(const unsigned char* data, size_t len)
      : d(data), n(len), pos(0) {}

  void Fill() {
    // fast path: when the next 8 bytes hold no 0xFF (no stuffing, no
    // marker — the overwhelmingly common case mid-scan), append all
    // the bytes that fit in one shift instead of branching per byte
    const int want = (64 - count) >> 3;
    if (want > 0 && pos + 8 <= n) {
      unsigned long long v;
      std::memcpy(&v, d + pos, 8);
      const unsigned long long m = ~v;  // 0xFF bytes of v become 0x00
      if (!((m - 0x0101010101010101ull) & ~m & 0x8080808080808080ull)) {
#if !defined(__BYTE_ORDER__) || __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
        v = __builtin_bswap64(v);  // byte 0 first -> MSB first
#endif  // big-endian memcpy already has byte 0 in the MSB
        // want == 8 only when count == 0: plain assign (acc << 64 is UB)
        acc = want == 8 ? v
                        : (acc << (want * 8)) | (v >> (64 - want * 8));
        pos += want;
        count += want * 8;
        return;
      }
    }
    while (count <= 56) {
      unsigned char b;
      if (pos >= n) {
        starved = true;
        b = 0;  // zero-pad: the trailing EOB bits of the last MCU may
                // legitimately read a few bits past the data end
      } else {
        b = d[pos];
        if (b == 0xFF) {
          if (pos + 1 < n && d[pos + 1] == 0x00) {
            pos += 2;  // stuffed zero
          } else {
            starved = true;  // a real marker: stop consuming bytes
            b = 0;
          }
        } else {
          ++pos;
        }
      }
      acc = (acc << 8) | b;
      count += 8;
    }
  }

  inline int Peek(int nbits) {
    if (count < nbits) Fill();
    return static_cast<int>((acc >> (count - nbits)) &
                            ((1ull << nbits) - 1));
  }

  inline void Drop(int nbits) { count -= nbits; }

  inline int GetBits(int nbits) {
    if (nbits == 0) return 0;
    const int v = Peek(nbits);
    count -= nbits;
    return v;
  }

  // byte-align and consume an expected RSTn marker (0xD0..0xD7)
  bool ConsumeRestart() {
    count = 0;
    acc = 0;
    starved = false;
    if (pos + 1 >= n || d[pos] != 0xFF) return false;
    const unsigned char m = d[pos + 1];
    if (m < 0xD0 || m > 0xD7) return false;
    pos += 2;
    return true;
  }
};

inline int HuffDecode(BitReader* br, const HuffTable& t) {
  const unsigned short hit = t.lut[br->Peek(8)];
  if (hit) {
    br->Drop(hit >> 8);
    return hit & 0xFF;
  }
  // slow path: codes longer than 8 bits (rare with standard tables)
  int code = br->Peek(8);
  int consumed = 8;
  for (int l = 9; l <= 16; ++l) {
    code = (code << 1) | ((br->Peek(l) & 1));
    consumed = l;
    // both bounds: a malformed DHT can otherwise admit codes below
    // mincode[l], indexing values[] at a negative offset
    if (t.maxcode[l] >= 0 && code >= t.mincode[l] &&
        code <= t.maxcode[l]) {
      br->Drop(consumed);
      return t.values[t.valptr[l] + code - t.mincode[l]];
    }
  }
  return -1;  // invalid code
}

inline int Extend(int v, int s) {
  return (s && v < (1 << (s - 1))) ? v - (1 << s) + 1 : v;
}

// AAN per-coefficient scale factors s[k] = sqrt(2) cos(k pi/16)
// (s[0] = 1), folded into the dequant tables together with the /8
// normalization so the per-block transform needs only 5 multiplies
// per 1-D pass instead of a full 8x8 matrix product.
constexpr float kAanScale[8] = {
    1.0f, 1.387039845f, 1.306562965f, 1.175875602f,
    1.0f, 0.785694958f, 0.541196100f, 0.275899379f};

// FMA contraction is re-enabled here (the file-level -ffp-contract=off
// exists for the y4m RGB conversion's bit-exact numpy parity, which
// the IDCT does not participate in).
#pragma GCC push_options
#pragma GCC optimize("fp-contract=fast")

// One 1-D pass of the AAN inverse (Arai–Agui–Nakajima scaled IDCT):
// inputs are coefficients pre-scaled by kAanScale[u]*kAanScale[v]/8.
// Butterfly validated against the direct cosine-matrix IDCT to float
// precision (see the numpy derivation in tests/test_mjpeg.py history).
inline void AanIdct1D(const float* in, int is, float* out, int os) {
  const float x0 = in[0], x1 = in[1 * is], x2 = in[2 * is],
              x3 = in[3 * is], x4 = in[4 * is], x5 = in[5 * is],
              x6 = in[6 * is], x7 = in[7 * is];
  const float p0 = x0 + x4, p1 = x0 - x4;
  const float p2 = x2 + x6;
  const float p3 = (x2 - x6) * 1.414213562f - p2;
  const float e0 = p0 + p2, e3 = p0 - p2;
  const float e1 = p1 + p3, e2 = p1 - p3;
  const float z13 = x5 + x3, z10 = x5 - x3;
  const float z11 = x1 + x7, z12 = x1 - x7;
  const float t7 = z11 + z13;
  const float t11 = (z11 - z13) * 1.414213562f;
  const float z5 = (z10 + z12) * 1.847759065f;
  const float t10 = 1.082392200f * z12 - z5;
  const float t12 = -2.613125930f * z10 + z5;
  const float t6 = t12 - t7;
  const float t5 = t11 - t6;
  const float t4 = t10 + t5;
  out[0] = e0 + t7;
  out[7 * os] = e0 - t7;
  out[1 * os] = e1 + t6;
  out[6 * os] = e1 - t6;
  out[2 * os] = e2 + t5;
  out[5 * os] = e2 - t5;
  out[4 * os] = e3 + t4;
  out[3 * os] = e3 - t4;
}

// row_mask: bit v set when coefficient row v has any nonzero entry —
// zero rows produce zero intermediate rows and skip their pass-1
// butterfly (most blocks at typical qualities populate only the
// first few rows).
void Idct8x8(const float* blk, int row_mask, unsigned char* out,
             int out_stride) {
  float tmp[64];
  for (int v = 0; v < 8; ++v) {
    if (!(row_mask & (1 << v))) {
      std::memset(tmp + v * 8, 0, 8 * sizeof(float));
      continue;
    }
    AanIdct1D(blk + v * 8, 1, tmp + v * 8, 1);
  }
  float cols[64];  // cols[y][x]
  for (int x = 0; x < 8; ++x)
    AanIdct1D(tmp + x, 8, cols + x, 8);
  for (int y = 0; y < 8; ++y) {
    unsigned char* orow = out + y * out_stride;
    const float* arow = cols + y * 8;
    for (int x = 0; x < 8; ++x) {
      const float px = arow[x] + 128.0f;
      orow[x] = ClipByte(px < 0.f ? 0.f : (px + 0.5f));  // round half up
    }
  }
}
#pragma GCC pop_options

struct JpegComponent {
  int id = 0, h = 1, v = 1, tq = 0, td = 0, ta = 0;
  int plane_w = 0, plane_h = 0;  // MCU-padded
  std::vector<unsigned char> plane;
};

// DCT-coefficient decode mode (pixel_path "dct", rnb_tpu/ops/dct.py):
// the entropy decode stops at dequantized zigzag coefficients — no
// Idct8x8, no pixel planes, the per-pixel host work this mode exists
// to delete. Blocks land plane-major (Y raster, then U, then V) so
// the packed wire stream is container-order independent of the MCU
// interleave.
struct CoeffSink {
  std::vector<short> dense;  // nb x 64, zigzag order within a block
  std::vector<int> last;     // highest zigzag index written per block
  int nb = 0;
  int blocks_w_y = 0;        // luma blocks per row
  int ny = 0;                // luma block count
  int nc = 0;                // per-chroma-plane block count

  void Reset(int w, int h) {
    blocks_w_y = w / 8;
    ny = (h / 8) * blocks_w_y;
    nc = (h / 16) * (w / 16);
    nb = ny + 2 * nc;
    dense.assign(static_cast<size_t>(nb) * 64, 0);
    last.assign(nb, 0);
  }
};

inline short ClampCoeff(float v) {
  if (v < -32768.f) v = -32768.f;
  if (v > 32767.f) v = 32767.f;
  return static_cast<short>(v);
}

// Pack one decoded frame's coefficients into the wire row layout
// (rnb_tpu/ops/dct.py): per-block nonzero counts, then values, then
// zigzag positions, padded with zeros to `capacity` entries each.
// kErrBudget when the frame's spectrum does not fit — truncating it
// would silently change pixels, so the caller surfaces a classified
// error instead.
int PackCoeffFrame(const CoeffSink& sink, int capacity, short* out) {
  const int nb = sink.nb;
  std::memset(out, 0,
              sizeof(short) * (static_cast<size_t>(nb) + 2 * capacity));
  int cursor = 0;
  for (int b = 0; b < nb; ++b) {
    const short* drow = sink.dense.data() + static_cast<size_t>(b) * 64;
    int cnt = 0;
    for (int k = 0; k <= sink.last[b]; ++k) {
      if (!drow[k]) continue;
      if (cursor >= capacity) return kErrBudget;
      out[nb + cursor] = drow[k];
      out[nb + capacity + cursor] = static_cast<short>(k);
      ++cursor;
      ++cnt;
    }
    out[b] = static_cast<short>(cnt);
  }
  return 0;
}

// Decode one baseline JPEG into planar samples at source geometry.
// On success fills width/height/subsample and the payload vector in
// y4m plane order (Y, then Cb, Cr at w/sub x h/sub).
// With `sink` non-null the decode STOPS at entropy-decoded,
// dequantized zigzag coefficients (plain integer dequant, no AAN
// scale fold, no IDCT, no pixel planes) — the pixel_path "dct" cut
// point; payload is untouched and 4:2:0 whole-MCU geometry is
// required.
int DecodeJpegFrame(const unsigned char* data, size_t n, int* width,
                    int* height, int* subsample,
                    std::vector<unsigned char>* payload,
                    CoeffSink* sink = nullptr) {
  if (n < 4 || data[0] != 0xFF || data[1] != 0xD8) return kErrFormat;
  unsigned short qt[4][64];
  bool qt_ok[4] = {false, false, false, false};
  HuffTable hdc[4], hac[4];
  JpegComponent comps[3];
  int ncomp = 0, w = 0, h = 0, restart_interval = 0;
  size_t p = 2;
  bool sos = false;
  size_t scan_start = 0;
  while (!sos) {
    // find the next marker (skip fill bytes)
    while (p < n && data[p] != 0xFF) ++p;
    while (p < n && data[p] == 0xFF) ++p;
    if (p >= n) return kErrFormat;
    const unsigned char m = data[p];
    ++p;
    if (m == 0xD9) return kErrFormat;  // EOI before SOS
    if (m >= 0xD0 && m <= 0xD7) continue;  // stray RST
    if (p + 2 > n) return kErrFormat;
    const size_t seg_len = (data[p] << 8) | data[p + 1];
    if (seg_len < 2 || p + seg_len > n) return kErrFormat;
    const unsigned char* seg = data + p + 2;
    const size_t seg_n = seg_len - 2;
    switch (m) {
      case 0xDB: {  // DQT: one or more tables
        size_t q = 0;
        while (q < seg_n) {
          const int pq = seg[q] >> 4, tq_id = seg[q] & 15;
          ++q;
          if (tq_id > 3) return kErrFormat;
          const size_t need = pq ? 128 : 64;
          if (q + need > seg_n) return kErrFormat;
          for (int k = 0; k < 64; ++k)
            qt[tq_id][k] = pq ? ((seg[q + 2 * k] << 8) | seg[q + 2 * k + 1])
                              : seg[q + k];
          qt_ok[tq_id] = true;
          q += need;
        }
        break;
      }
      case 0xC4: {  // DHT: one or more tables
        size_t q = 0;
        while (q + 17 <= seg_n) {
          const int tc = seg[q] >> 4, th = seg[q] & 15;
          if (th > 3 || tc > 1) return kErrFormat;
          int nvals = 0;
          for (int i = 0; i < 16; ++i) nvals += seg[q + 1 + i];
          if (q + 17 + nvals > seg_n || nvals > 256) return kErrFormat;
          (tc ? hac[th] : hdc[th]).Build(seg + q + 1, seg + q + 17,
                                         nvals);
          q += 17 + nvals;
        }
        break;
      }
      case 0xC0:
      case 0xC1: {  // baseline / extended-sequential Huffman SOF
        if (seg_n < 6 || seg[0] != 8) return kErrFormat;  // 8-bit only
        h = (seg[1] << 8) | seg[2];
        w = (seg[3] << 8) | seg[4];
        ncomp = seg[5];
        if (w <= 0 || h <= 0 || ncomp != 3) return kErrColorspace;
        if (seg_n < 6 + static_cast<size_t>(ncomp) * 3) return kErrFormat;
        for (int c = 0; c < ncomp; ++c) {
          comps[c].id = seg[6 + c * 3];
          comps[c].h = seg[7 + c * 3] >> 4;
          comps[c].v = seg[7 + c * 3] & 15;
          comps[c].tq = seg[8 + c * 3];
          // Tq indexes qt[4]/fq[4]: an unvalidated byte here would be
          // an out-of-bounds indexed WRITE when fq is built
          if (comps[c].tq > 3) return kErrFormat;
        }
        break;
      }
      case 0xC2:
        return kErrColorspace;  // progressive unsupported
      case 0xDD: {  // DRI
        if (seg_n < 2) return kErrFormat;
        restart_interval = (seg[0] << 8) | seg[1];
        break;
      }
      case 0xDA: {  // SOS
        if (seg_n < 1) return kErrFormat;
        const int ns = seg[0];
        if (ns != ncomp || seg_n < 1 + static_cast<size_t>(ns) * 2 + 3)
          return kErrFormat;
        for (int s = 0; s < ns; ++s) {
          const int cs = seg[1 + s * 2];
          const int td = seg[2 + s * 2] >> 4;
          const int ta = seg[2 + s * 2] & 15;
          // Td/Ta index hdc[4]/hac[4]
          if (td > 3 || ta > 3) return kErrFormat;
          for (int c = 0; c < ncomp; ++c)
            if (comps[c].id == cs) {
              comps[c].td = td;
              comps[c].ta = ta;
            }
        }
        sos = true;
        scan_start = p + seg_len;
        break;
      }
      default:
        break;  // APPn / COM / anything else: skip
    }
    p += seg_len;
  }
  if (w <= 0 || h <= 0) return kErrFormat;
  // sampling: 4:2:0 = (2,2)(1,1)(1,1); 4:4:4 = all (1,1)
  int sub;
  if (comps[0].h == 2 && comps[0].v == 2 && comps[1].h == 1 &&
      comps[1].v == 1 && comps[2].h == 1 && comps[2].v == 1) {
    sub = 2;
    if (w % 2 || h % 2) return kErrColorspace;  // match y4m 4:2:0
  } else if (comps[0].h == 1 && comps[0].v == 1 && comps[1].h == 1 &&
             comps[1].v == 1 && comps[2].h == 1 && comps[2].v == 1) {
    sub = 1;
  } else {
    return kErrColorspace;
  }
  if (sink != nullptr) {
    // the coefficient wire format is 4:2:0 whole-MCU only: no resize
    // exists in the coefficient domain, so partial edge blocks would
    // ship spectrum for pixels the consumer never shows
    if (sub != 2) return kErrColorspace;
    if (w % 16 || h % 16) return kErrColorspace;
    sink->Reset(w, h);
  }
  const int maxh = comps[0].h, maxv = comps[0].v;
  const int mcus_x = (w + 8 * maxh - 1) / (8 * maxh);
  const int mcus_y = (h + 8 * maxv - 1) / (8 * maxv);
  for (int c = 0; c < ncomp; ++c) {
    if (!qt_ok[comps[c].tq] || !hdc[comps[c].td].present ||
        !hac[comps[c].ta].present)
      return kErrFormat;
    if (sink != nullptr) continue;  // no pixel planes in coeff mode
    comps[c].plane_w = mcus_x * comps[c].h * 8;
    comps[c].plane_h = mcus_y * comps[c].v * 8;
    comps[c].plane.assign(
        static_cast<size_t>(comps[c].plane_w) * comps[c].plane_h, 0);
  }
  // dequant tables, indexed in zigzag scan order like the raw tables;
  // pixel mode folds in the AAN scale factors and /8 normalization,
  // coefficient mode keeps the RAW quantizer (plain integer dequant —
  // the values are exact small integers in float). Built AFTER the
  // qt_ok validation so an undefined table never feeds the fold.
  float fq[4][64];
  for (int c = 0; c < ncomp; ++c) {
    const int tq_id = comps[c].tq;
    for (int k = 0; k < 64; ++k) {
      const int nat = kZigzag[k];
      fq[tq_id][k] = sink != nullptr
                         ? static_cast<float>(qt[tq_id][k])
                         : static_cast<float>(qt[tq_id][k]) *
                               kAanScale[nat >> 3] * kAanScale[nat & 7] /
                               8.0f;
    }
  }
  BitReader br(data + scan_start, n - scan_start);
  int dc_pred[3] = {0, 0, 0};
  float blk[64];
  int mcus_until_restart = restart_interval;
  for (int my = 0; my < mcus_y; ++my) {
    for (int mx = 0; mx < mcus_x; ++mx) {
      if (restart_interval && mcus_until_restart == 0) {
        if (!br.ConsumeRestart()) return kErrFormat;
        dc_pred[0] = dc_pred[1] = dc_pred[2] = 0;
        mcus_until_restart = restart_interval;
      }
      if (restart_interval) --mcus_until_restart;
      for (int c = 0; c < ncomp; ++c) {
        JpegComponent& comp = comps[c];
        const float* q = fq[comp.tq];
        for (int by = 0; by < comp.v; ++by) {
          for (int bx = 0; bx < comp.h; ++bx) {
            // entropy-decode one block
            const int t = HuffDecode(&br, hdc[comp.td]);
            if (t < 0 || t > 11) return kErrFormat;
            const int diff = Extend(br.GetBits(t), t);
            dc_pred[c] += diff;
            std::memset(blk, 0, sizeof(blk));
            blk[0] = static_cast<float>(dc_pred[c]) * q[0];
            int k = 1, row_mask = 1, last_k = 0;
            bool ac_any = false;
            const HuffTable& act = hac[comp.ta];
            while (k < 64) {
              // fused lookahead: symbol AND its value bits from one
              // 24-bit peek when the 8-bit LUT hits (libjpeg-turbo's
              // arrangement); falls back to the generic path otherwise
              int rs;
              const int look = br.Peek(24);
              const unsigned short hit = act.lut[look >> 16];
              if (hit) {
                const int hlen = hit >> 8;
                rs = hit & 0xFF;
                const int s_ = rs & 15;
                if (s_) {
                  const int r_ = rs >> 4;
                  k += r_;
                  if (k > 63) return kErrFormat;
                  const int vraw =
                      (look >> (24 - hlen - s_)) & ((1 << s_) - 1);
                  br.Drop(hlen + s_);
                  const int nat = kZigzag[k];
                  blk[nat] =
                      static_cast<float>(Extend(vraw, s_)) * q[k];
                  row_mask |= 1 << (nat >> 3);
                  ac_any = true;
                  last_k = k;
                  ++k;
                  continue;
                }
                br.Drop(hlen);
              } else {
                rs = HuffDecode(&br, act);
                if (rs < 0) return kErrFormat;
                const int s_ = rs & 15;
                if (s_) {
                  k += rs >> 4;
                  if (k > 63) return kErrFormat;
                  const int nat = kZigzag[k];
                  blk[nat] = static_cast<float>(
                      Extend(br.GetBits(s_), s_)) * q[k];
                  row_mask |= 1 << (nat >> 3);
                  ac_any = true;
                  last_k = k;
                  ++k;
                  continue;
                }
              }
              if ((rs >> 4) == 15) {
                k += 16;  // ZRL
                continue;
              }
              break;  // EOB
            }
            if (sink != nullptr) {
              // coefficient mode: the block's dequantized zigzag
              // prefix IS the output — blk holds exact integers
              // (raw value x raw quantizer) in natural order
              const int bidx =
                  c == 0 ? (my * comp.v + by) * sink->blocks_w_y +
                               (mx * comp.h + bx)
                         : sink->ny + (c - 1) * sink->nc +
                               my * mcus_x + mx;
              short* drow =
                  sink->dense.data() + static_cast<size_t>(bidx) * 64;
              for (int k2 = 0; k2 <= last_k; ++k2)
                drow[k2] = ClampCoeff(blk[kZigzag[k2]]);
              sink->last[bidx] = last_k;
              continue;
            }
            const int px = (mx * comp.h + bx) * 8;
            const int py = (my * comp.v + by) * 8;
            unsigned char* dst8 =
                comp.plane.data() +
                static_cast<size_t>(py) * comp.plane_w + px;
            if (!ac_any) {
              // DC-only block: the IDCT collapses to a flat fill
              // the folded dequant already carries the /8
              const float px0 = blk[0] + 128.0f;
              const unsigned char flat =
                  ClipByte(px0 < 0.f ? 0.f : (px0 + 0.5f));
              for (int ry = 0; ry < 8; ++ry)
                std::memset(dst8 + static_cast<size_t>(ry) * comp.plane_w,
                            flat, 8);
            } else {
              Idct8x8(blk, row_mask, dst8, comp.plane_w);
            }
          }
        }
      }
    }
  }
  if (sink != nullptr) {
    // coefficient mode: no pixel payload to crop
    *width = w;
    *height = h;
    *subsample = sub;
    return 0;
  }
  // crop the MCU-padded planes into the packed y4m payload layout
  const int cw = w / sub, chh = h / sub;
  payload->resize(static_cast<size_t>(w) * h +
                  2 * static_cast<size_t>(cw) * chh);
  unsigned char* dst = payload->data();
  for (int r = 0; r < h; ++r)
    std::memcpy(dst + static_cast<size_t>(r) * w,
                comps[0].plane.data() +
                    static_cast<size_t>(r) * comps[0].plane_w,
                w);
  dst += static_cast<size_t>(w) * h;
  for (int c = 1; c < 3; ++c) {
    for (int r = 0; r < chh; ++r)
      std::memcpy(dst + static_cast<size_t>(r) * cw,
                  comps[c].plane.data() +
                      static_cast<size_t>(r) * comps[c].plane_w,
                  cw);
    dst += static_cast<size_t>(cw) * chh;
  }
  *width = w;
  *height = h;
  *subsample = sub;
  return 0;
}

// ---------------------------------------------------------------------------
// MJPEG container: concatenated baseline JPEGs. Frame boundaries are
// found by walking the marker structure: length-prefixed segments are
// skipped whole (an APPn/EXIF payload may legally contain FF D9 — a
// thumbnail's EOI — so a raw byte scan would split mid-frame), and
// only inside entropy-coded data (where every 0xFF is 0x00-stuffed or
// an RST) is FF D9 unambiguous.

// -> offset one past this frame's EOI, or 0 when the frame structure
// is corrupt/truncated. d[p..] must start at an SOI.
size_t JpegFrameEnd(const unsigned char* d, size_t n, size_t p) {
  p += 2;  // SOI
  while (p + 1 < n) {
    if (d[p] != 0xFF) return 0;
    while (p < n && d[p] == 0xFF) ++p;  // fill bytes
    if (p >= n) return 0;
    const unsigned char m = d[p++];
    if (m == 0xD9) return p;  // EOI
    if (m == 0x01 || (m >= 0xD0 && m <= 0xD7)) continue;  // TEM/RSTn
    if (p + 2 > n) return 0;
    const size_t len = (static_cast<size_t>(d[p]) << 8) | d[p + 1];
    if (len < 2 || p + len > n) return 0;
    const bool is_sos = (m == 0xDA);
    p += len;
    if (is_sos) {
      // entropy-coded data: advance to the next real marker
      while (p + 1 < n) {
        if (d[p] != 0xFF) {
          ++p;
        } else if (d[p + 1] == 0x00 ||
                   (d[p + 1] >= 0xD0 && d[p + 1] <= 0xD7)) {
          p += 2;  // stuffing / restart
        } else if (d[p + 1] == 0xFF) {
          ++p;  // fill byte
        } else {
          break;  // real marker: handled by the loop top
        }
      }
      if (p + 1 >= n) return 0;
    }
  }
  return 0;
}

struct MjpegIndex {
  int width = 0, height = 0, subsample = 1;
  std::vector<long long> offsets;  // frame start (SOI)
  std::vector<long long> lengths;  // through EOI
  long long file_size = 0;
  long long mtime_ns = 0;
};

int ScanMjpeg(const char* path, MjpegIndex* idx) {
  FILE* f = fopen(path, "rb");
  if (!f) return kErrIo;
  if (fseeko(f, 0, SEEK_END) != 0) {
    fclose(f);
    return kErrIo;
  }
  const long long size = ftello(f);
  std::vector<unsigned char> data(static_cast<size_t>(size));
  if (fseeko(f, 0, SEEK_SET) != 0 ||
      fread(data.data(), 1, data.size(), f) != data.size()) {
    fclose(f);
    return kErrIo;
  }
  fclose(f);
  idx->offsets.clear();
  idx->lengths.clear();
  size_t p = 0;
  const size_t n = data.size();
  while (p + 3 < n) {
    if (data[p] == 0xFF && data[p + 1] == 0xD8 && data[p + 2] == 0xFF) {
      const size_t end = JpegFrameEnd(data.data(), n, p);
      if (!end) break;  // truncated trailing frame: drop it
      idx->offsets.push_back(static_cast<long long>(p));
      idx->lengths.push_back(static_cast<long long>(end - p));
      p = end;
    } else {
      ++p;
    }
  }
  if (idx->offsets.empty()) return kErrFormat;
  // geometry from the first frame (MJPEG semantics: constant geometry)
  int w, h, sub;
  std::vector<unsigned char> payload;
  const int rc = DecodeJpegFrame(
      data.data() + idx->offsets[0],
      static_cast<size_t>(idx->lengths[0]), &w, &h, &sub, &payload);
  if (rc != 0) return rc;
  idx->width = w;
  idx->height = h;
  idx->subsample = sub;
  idx->file_size = size;
  return 0;
}

// index cache: rescanning a multi-MB file per decode call would cost
// more than the decode of a short clip list. Entries are validated by
// (size, mtime) so an in-place regeneration of the file — even to the
// same byte count — invalidates the cached frame offsets.
std::mutex g_mjpeg_mu;
std::map<std::string, MjpegIndex> g_mjpeg_cache;

int StatFile(const char* path, long long* size, long long* mtime_ns) {
  struct stat st;
  if (stat(path, &st) != 0) return kErrIo;
  *size = static_cast<long long>(st.st_size);
  *mtime_ns = static_cast<long long>(st.st_mtim.tv_sec) * 1000000000ll +
              st.st_mtim.tv_nsec;
  return 0;
}

int GetMjpegIndex(const char* path, MjpegIndex* out) {
  long long size, mtime_ns;
  int rc = StatFile(path, &size, &mtime_ns);
  if (rc != 0) return rc;
  {
    std::lock_guard<std::mutex> lk(g_mjpeg_mu);
    auto it = g_mjpeg_cache.find(path);
    if (it != g_mjpeg_cache.end() && it->second.file_size == size &&
        it->second.mtime_ns == mtime_ns) {
      *out = it->second;
      return 0;
    }
  }
  MjpegIndex idx;
  rc = ScanMjpeg(path, &idx);
  if (rc != 0) return rc;
  idx.mtime_ns = mtime_ns;
  {
    std::lock_guard<std::mutex> lk(g_mjpeg_mu);
    g_mjpeg_cache[path] = idx;
  }
  *out = idx;
  return 0;
}

// 0 = y4m, 1 = mjpeg, <0 = error
int SniffContainer(const char* path) {
  FILE* f = fopen(path, "rb");
  if (!f) return kErrIo;
  unsigned char magic[9] = {0};
  const size_t got = fread(magic, 1, sizeof(magic), f);
  fclose(f);
  if (got >= 9 && std::memcmp(magic, "YUV4MPEG2", 9) == 0) return 0;
  if (got >= 3 && magic[0] == 0xFF && magic[1] == 0xD8 &&
      magic[2] == 0xFF)
    return 1;
  return kErrFormat;
}

int DecodeClipsMjpeg(const char* path, const long long* clip_starts,
                     int num_clips, int consecutive, int out_w,
                     int out_h, unsigned char* out, int pixfmt,
                     int dct_capacity);

// Convert one source frame payload into the caller's RGB output tile,
// fusing nearest chroma upsample + box resize (out[r][c] samples
// source pixel (r*h/out_h, c*w/out_w) — the numpy backend's index map).
// The column index maps are loop-invariant across rows (and frames of
// the same geometry), so they are hoisted: the hot loop was paying a
// 64-bit division per output pixel, which dominated decode on the
// 1-core benchmark host.
void ConvertFrame(const unsigned char* payload, const Y4mMeta& m,
                  int out_w, int out_h, unsigned char* out,
                  std::vector<int>* col_map_storage) {
  const int w = m.width, h = m.height, sub = m.subsample;
  const int cw = w / sub;
  const unsigned char* yp = payload;
  const unsigned char* up = payload + static_cast<long long>(w) * h;
  const unsigned char* vp = up + static_cast<long long>(cw) * (h / sub);
  // [0..out_w) luma column, [out_w..2*out_w) chroma column, then the
  // 3-entry cache key (w, sub, out_w) — the map depends on all three,
  // so geometry changes between calls rebuild instead of silently
  // reusing stale indices
  std::vector<int>& cols = *col_map_storage;
  if (cols.size() != static_cast<size_t>(out_w) * 2 + 3 ||
      cols[out_w * 2] != w || cols[out_w * 2 + 1] != sub ||
      cols[out_w * 2 + 2] != out_w) {
    cols.resize(static_cast<size_t>(out_w) * 2 + 3);
    for (int c = 0; c < out_w; ++c) {
      const int sx = static_cast<int>(
          static_cast<long long>(c) * w / out_w);
      cols[c] = sx;
      cols[out_w + c] = sx / sub;
    }
    cols[out_w * 2] = w;
    cols[out_w * 2 + 1] = sub;
    cols[out_w * 2 + 2] = out_w;
  }
  const int* lcol = cols.data();
  const int* ccol = cols.data() + out_w;
  // chroma contributions depend only on the 8-bit sample: precompute
  // the four products once (bit-identical to the inline multiplies,
  // and the additions keep the numpy backend's left-to-right order so
  // the two backends stay bit-exact)
  static const struct ChromaLut {
    float rv[256], gu[256], gv[256], bu[256];
    ChromaLut() {
      for (int i = 0; i < 256; ++i) {
        const float f = static_cast<float>(i) - 128.0f;
        rv[i] = 1.402f * f;
        gu[i] = -0.344136f * f;
        gv[i] = -0.714136f * f;
        bu[i] = 1.772f * f;
      }
    }
  } lut;
  for (int r = 0; r < out_h; ++r) {
    const int sy = static_cast<int>(
        static_cast<long long>(r) * h / out_h);
    const unsigned char* yrow = yp + static_cast<long long>(sy) * w;
    const unsigned char* urow = up + static_cast<long long>(sy / sub) * cw;
    const unsigned char* vrow = vp + static_cast<long long>(sy / sub) * cw;
    unsigned char* orow = out + static_cast<long long>(r) * out_w * 3;
    for (int c = 0; c < out_w; ++c) {
      const float yf = static_cast<float>(yrow[lcol[c]]);
      const unsigned char u = urow[ccol[c]];
      const unsigned char v = vrow[ccol[c]];
      orow[c * 3 + 0] = ClipByte(yf + lut.rv[v]);
      orow[c * 3 + 1] = ClipByte((yf + lut.gu[u]) + lut.gv[v]);
      orow[c * 3 + 2] = ClipByte(yf + lut.bu[u]);
    }
  }
}

// Gather one source frame into packed output-resolution 4:2:0 planes:
// Y (out_h x out_w) then U, V (out_h/2 x out_w/2 each), concatenated.
// No float math happens on the host in this pixel path — chroma
// upsample + BT.601 conversion run on the accelerator, fused into the
// ingest preprocess (rnb_tpu/ops/yuv.py). Luma uses the same
// nearest-neighbour index map as ConvertFrame (bit-exact with the RGB
// path); chroma keeps its own nearest map at half output resolution,
// the standard 4:2:0 semantics.
void GatherFrameYUV(const unsigned char* payload, const Y4mMeta& m,
                    int out_w, int out_h, unsigned char* out,
                    std::vector<int>* col_map_storage) {
  const int w = m.width, h = m.height, sub = m.subsample;
  const int cw = w / sub, ch = h / sub;
  const int half_w = out_w / 2, half_h = out_h / 2;
  const unsigned char* yp = payload;
  const unsigned char* up = payload + static_cast<long long>(w) * h;
  const unsigned char* vp = up + static_cast<long long>(cw) * ch;
  // [0..out_w) luma column map, [out_w..out_w+half_w) chroma column
  // map (against the source chroma plane), then the cache key — one
  // extra sentinel vs the RGB path's key so the two layouts can never
  // alias in a shared storage vector
  std::vector<int>& cols = *col_map_storage;
  const size_t want = static_cast<size_t>(out_w) + half_w + 4;
  if (cols.size() != want || cols[out_w + half_w] != w ||
      cols[out_w + half_w + 1] != sub ||
      cols[out_w + half_w + 2] != out_w ||
      cols[out_w + half_w + 3] != -2) {
    cols.resize(want);
    for (int c = 0; c < out_w; ++c)
      cols[c] = static_cast<int>(static_cast<long long>(c) * w / out_w);
    for (int c = 0; c < half_w; ++c)
      cols[out_w + c] =
          static_cast<int>(static_cast<long long>(c) * cw / half_w);
    cols[out_w + half_w] = w;
    cols[out_w + half_w + 1] = sub;
    cols[out_w + half_w + 2] = out_w;
    cols[out_w + half_w + 3] = -2;
  }
  const int* lcol = cols.data();
  const int* ccol = cols.data() + out_w;
  unsigned char* oy = out;
  unsigned char* ou = out + static_cast<long long>(out_h) * out_w;
  unsigned char* ov = ou + static_cast<long long>(half_h) * half_w;
  for (int r = 0; r < out_h; ++r) {
    const int sy = static_cast<int>(
        static_cast<long long>(r) * h / out_h);
    const unsigned char* yrow = yp + static_cast<long long>(sy) * w;
    unsigned char* orow = oy + static_cast<long long>(r) * out_w;
    for (int c = 0; c < out_w; ++c) orow[c] = yrow[lcol[c]];
  }
  for (int r = 0; r < half_h; ++r) {
    const int sy = static_cast<int>(
        static_cast<long long>(r) * ch / half_h);
    const unsigned char* urow = up + static_cast<long long>(sy) * cw;
    const unsigned char* vrow = vp + static_cast<long long>(sy) * cw;
    unsigned char* our = ou + static_cast<long long>(r) * half_w;
    unsigned char* ovr = ov + static_cast<long long>(r) * half_w;
    for (int c = 0; c < half_w; ++c) {
      our[c] = urow[ccol[c]];
      ovr[c] = vrow[ccol[c]];
    }
  }
}

constexpr int kPixRgb = 0;     // fused convert+resize, RGB u8 out
constexpr int kPixYuv420 = 1;  // gather-only, packed 4:2:0 planes out
constexpr int kPixDct = 2;     // dequantized coefficients, int16 rows

int DecodeClips(const char* path, const long long* clip_starts,
                int num_clips, int consecutive, int out_w, int out_h,
                unsigned char* out, int pixfmt = kPixRgb,
                int dct_capacity = 0) {
  if (num_clips < 0 || consecutive <= 0 || out_w <= 0 || out_h <= 0 ||
      out == nullptr)
    return kErrArg;
  if (pixfmt != kPixRgb && pixfmt != kPixYuv420 && pixfmt != kPixDct)
    return kErrArg;
  if (pixfmt == kPixYuv420 && (out_w % 2 != 0 || out_h % 2 != 0))
    return kErrArg;  // packed 4:2:0 needs even output geometry
  if (pixfmt == kPixDct &&
      (dct_capacity < 1 || out_w % 16 != 0 || out_h % 16 != 0))
    return kErrArg;  // coefficient rows need whole-MCU geometry
  const int container = SniffContainer(path);
  if (container < 0) return container;
  if (container == 1)
    return DecodeClipsMjpeg(path, clip_starts, num_clips, consecutive,
                            out_w, out_h, out, pixfmt, dct_capacity);
  if (pixfmt == kPixDct)
    return kErrFormat;  // uncompressed y4m carries no coefficients
  Y4mMeta m;
  int rc = ProbeFile(path, &m);
  if (rc != 0) return rc;
  FILE* f = fopen(path, "rb");
  if (!f) return kErrIo;
  std::vector<unsigned char> payload(
      static_cast<size_t>(m.frame_bytes));
  std::vector<int> col_map;  // reused across every frame of this call
  const long long frame_out =
      pixfmt == kPixYuv420
          ? static_cast<long long>(out_h) * out_w * 3 / 2
          : static_cast<long long>(out_h) * out_w * 3;
  long long last_idx = -1;
  for (int ci = 0; ci < num_clips; ++ci) {
    if (clip_starts[ci] < 0) {
      fclose(f);
      return kErrArg;  // numpy backend rejects these too
    }
    for (int fi = 0; fi < consecutive; ++fi) {
      long long idx = clip_starts[ci] + fi;
      if (idx > m.count - 1) idx = m.count - 1;  // clamp like numpy
      unsigned char* dst =
          out + (static_cast<long long>(ci) * consecutive + fi) * frame_out;
      if (idx != last_idx) {
        if (fseeko(f, m.data_start + idx * m.stride + m.marker_len,
                   SEEK_SET) != 0 ||
            fread(payload.data(), 1, payload.size(), f) !=
                payload.size()) {
          fclose(f);
          return kErrIo;
        }
        last_idx = idx;
        if (pixfmt == kPixYuv420)
          GatherFrameYUV(payload.data(), m, out_w, out_h, dst, &col_map);
        else
          ConvertFrame(payload.data(), m, out_w, out_h, dst, &col_map);
      } else {
        // consecutive repeats of the clamped last frame: copy the
        // previous converted output instead of re-decoding
        std::memcpy(dst, dst - frame_out, frame_out);
      }
    }
  }
  fclose(f);
  return 0;
}

// MJPEG leg of DecodeClips: per needed frame, Huffman+IDCT-decode the
// JPEG into a planar payload, then run the SAME fused convert/gather
// as the y4m path. Clamp-past-end and repeat-frame memcpy semantics
// are identical to the y4m leg (and the numpy backend).
int DecodeClipsMjpeg(const char* path, const long long* clip_starts,
                     int num_clips, int consecutive, int out_w,
                     int out_h, unsigned char* out, int pixfmt,
                     int dct_capacity) {
  MjpegIndex idx;
  int rc = GetMjpegIndex(path, &idx);
  if (rc != 0) return rc;
  if (pixfmt == kPixDct &&
      (idx.width != out_w || idx.height != out_h))
    // no resize exists in the coefficient domain: the caller must ask
    // for exactly the source geometry
    return kErrColorspace;
  FILE* f = fopen(path, "rb");
  if (!f) return kErrIo;
  Y4mMeta m;  // geometry carrier for the shared convert/gather stages
  m.width = idx.width;
  m.height = idx.height;
  m.subsample = idx.subsample;
  m.count = static_cast<long long>(idx.offsets.size());
  std::vector<unsigned char> compressed, payload;
  std::vector<int> col_map;
  CoeffSink sink;
  const long long frame_out =
      pixfmt == kPixDct
          ? (static_cast<long long>((out_h / 8) * (out_w / 8) +
                                    2 * (out_h / 16) * (out_w / 16)) +
             2 * dct_capacity) *
                static_cast<long long>(sizeof(short))
          : pixfmt == kPixYuv420
                ? static_cast<long long>(out_h) * out_w * 3 / 2
                : static_cast<long long>(out_h) * out_w * 3;
  long long last_idx = -1;
  for (int ci = 0; ci < num_clips; ++ci) {
    if (clip_starts[ci] < 0) {
      fclose(f);
      return kErrArg;
    }
    for (int fi = 0; fi < consecutive; ++fi) {
      long long idx_f = clip_starts[ci] + fi;
      if (idx_f > m.count - 1) idx_f = m.count - 1;
      unsigned char* dst =
          out + (static_cast<long long>(ci) * consecutive + fi) * frame_out;
      if (idx_f != last_idx) {
        compressed.resize(static_cast<size_t>(idx.lengths[idx_f]));
        if (fseeko(f, idx.offsets[idx_f], SEEK_SET) != 0 ||
            fread(compressed.data(), 1, compressed.size(), f) !=
                compressed.size()) {
          fclose(f);
          return kErrIo;
        }
        int w, h, sub;
        rc = DecodeJpegFrame(compressed.data(), compressed.size(), &w,
                             &h, &sub, &payload,
                             pixfmt == kPixDct ? &sink : nullptr);
        if (rc != 0 || w != m.width || h != m.height ||
            sub != m.subsample) {
          fclose(f);
          return rc != 0 ? rc : kErrFormat;
        }
        last_idx = idx_f;
        if (pixfmt == kPixDct) {
          rc = PackCoeffFrame(sink, dct_capacity,
                              reinterpret_cast<short*>(dst));
          if (rc != 0) {
            fclose(f);
            return rc;
          }
        } else if (pixfmt == kPixYuv420) {
          GatherFrameYUV(payload.data(), m, out_w, out_h, dst, &col_map);
        } else {
          ConvertFrame(payload.data(), m, out_w, out_h, dst, &col_map);
        }
      } else {
        std::memcpy(dst, dst - frame_out, frame_out);
      }
    }
  }
  fclose(f);
  return 0;
}

// ---------------------------------------------------------------------------
// Worker pool: submit() -> ticket, wait(ticket) -> rc.

struct Job {
  long long ticket;
  std::string path;
  std::vector<long long> starts;
  int consecutive, out_w, out_h;
  int pixfmt = kPixRgb;
  int dct_capacity = 0;  // per-frame coefficient budget (kPixDct only)
  unsigned char* out;
};

struct Pool {
  std::vector<std::thread> workers;
  std::deque<Job> jobs;
  std::map<long long, int> done;  // ticket -> rc
  std::mutex mu;
  std::condition_variable cv_job, cv_done;
  long long next_ticket = 1;
  bool stopping = false;
  // what the workers did since the pool was made (rnb_pool_stats):
  // nanoseconds inside DecodeClips summed over the workers, and the
  // frames of the jobs that succeeded
  std::atomic<long long> busy_ns{0}, frames_done{0};

  explicit Pool(int n) {
    for (int i = 0; i < n; ++i)
      workers.emplace_back([this] { Run(); });
  }

  void Run() {
    for (;;) {
      Job job;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_job.wait(lk, [this] { return stopping || !jobs.empty(); });
        if (jobs.empty()) return;  // stopping
        job = std::move(jobs.front());
        jobs.pop_front();
      }
      const auto t0 = std::chrono::steady_clock::now();
      const int rc = DecodeClips(
          job.path.c_str(), job.starts.data(),
          static_cast<int>(job.starts.size()), job.consecutive,
          job.out_w, job.out_h, job.out, job.pixfmt,
          job.dct_capacity);
      busy_ns += std::chrono::duration_cast<std::chrono::nanoseconds>(
                     std::chrono::steady_clock::now() - t0)
                     .count();
      if (rc == 0)
        frames_done +=
            static_cast<long long>(job.starts.size()) * job.consecutive;
      {
        std::lock_guard<std::mutex> lk(mu);
        done[job.ticket] = rc;
      }
      cv_done.notify_all();
    }
  }

  long long Submit(Job job) {
    long long t;
    {
      std::lock_guard<std::mutex> lk(mu);
      t = next_ticket++;
      job.ticket = t;
      jobs.push_back(std::move(job));
    }
    cv_job.notify_one();
    return t;
  }

  int Wait(long long ticket) {
    std::unique_lock<std::mutex> lk(mu);
    cv_done.wait(lk, [&] { return done.count(ticket) > 0; });
    const int rc = done[ticket];
    done.erase(ticket);
    return rc;
  }

  // Non-blocking: has this ticket finished? Does NOT retire it — the
  // result code stays queued for a later Wait().
  bool Peek(long long ticket) {
    std::lock_guard<std::mutex> lk(mu);
    return done.count(ticket) > 0;
  }

  ~Pool() {
    {
      std::lock_guard<std::mutex> lk(mu);
      stopping = true;
    }
    cv_job.notify_all();
    for (auto& w : workers) w.join();
  }
};

}  // namespace

extern "C" {

int rnb_y4m_probe(const char* path, int* width, int* height,
                  long long* num_frames) {
  const int container = SniffContainer(path);
  if (container < 0) return container;
  if (container == 1) {
    MjpegIndex idx;
    const int rc = GetMjpegIndex(path, &idx);
    if (rc != 0) return rc;
    if (width) *width = idx.width;
    if (height) *height = idx.height;
    if (num_frames)
      *num_frames = static_cast<long long>(idx.offsets.size());
    return 0;
  }
  Y4mMeta m;
  const int rc = ProbeFile(path, &m);
  if (rc != 0) return rc;
  if (width) *width = m.width;
  if (height) *height = m.height;
  if (num_frames) *num_frames = m.count;
  return 0;
}

// container-agnostic alias (y4m or mjpeg; sniffed). New export so a
// stale prebuilt library (without mjpeg support) fails the symbol
// check in rnb_tpu/decode/native.py and degrades cleanly.
int rnb_video_probe(const char* path, int* width, int* height,
                    long long* num_frames) {
  return rnb_y4m_probe(path, width, height, num_frames);
}

int rnb_y4m_decode_clips(const char* path, const long long* clip_starts,
                         int num_clips, int consecutive, int out_w,
                         int out_h, unsigned char* out) {
  return DecodeClips(path, clip_starts, num_clips, consecutive, out_w,
                     out_h, out);
}

// pixfmt: 0 = RGB (fused convert+resize), 1 = packed 4:2:0 planes
// (gather-only; out gets out_h*out_w*3/2 bytes per frame).
int rnb_y4m_decode_clips_fmt(const char* path,
                             const long long* clip_starts, int num_clips,
                             int consecutive, int out_w, int out_h,
                             int pixfmt, unsigned char* out) {
  return DecodeClips(path, clip_starts, num_clips, consecutive, out_w,
                     out_h, out, pixfmt);
}

void* rnb_pool_create(int num_threads) {
  if (num_threads <= 0) num_threads = 1;
  return new Pool(num_threads);
}

void rnb_pool_destroy(void* pool) { delete static_cast<Pool*>(pool); }

long long rnb_pool_submit(void* pool, const char* path,
                          const long long* clip_starts, int num_clips,
                          int consecutive, int out_w, int out_h,
                          unsigned char* out) {
  if (!pool || num_clips < 0) return -1;
  Job job;
  job.path = path;
  job.starts.assign(clip_starts, clip_starts + num_clips);
  job.consecutive = consecutive;
  job.out_w = out_w;
  job.out_h = out_h;
  job.out = out;
  return static_cast<Pool*>(pool)->Submit(std::move(job));
}

long long rnb_pool_submit_fmt(void* pool, const char* path,
                              const long long* clip_starts,
                              int num_clips, int consecutive, int out_w,
                              int out_h, int pixfmt,
                              unsigned char* out) {
  if (!pool || num_clips < 0) return -1;
  if (pixfmt != kPixRgb && pixfmt != kPixYuv420) return -1;
  Job job;
  job.path = path;
  job.starts.assign(clip_starts, clip_starts + num_clips);
  job.consecutive = consecutive;
  job.out_w = out_w;
  job.out_h = out_h;
  job.pixfmt = pixfmt;
  job.out = out;
  return static_cast<Pool*>(pool)->Submit(std::move(job));
}

// pixel_path "dct" (rnb_tpu/ops/dct.py): decode MJPEG clips stopping
// at dequantized DCT coefficients, packed into int16 wire rows of
// (num_blocks + 2 * coeff_capacity) elements per frame. out_w/out_h
// must equal the source geometry (divisible by 16, 4:2:0 only). New
// export: a stale prebuilt library fails the symbol check in
// rnb_tpu/decode/native.py and degrades cleanly.
int rnb_y4m_decode_clips_dct(const char* path,
                             const long long* clip_starts,
                             int num_clips, int consecutive, int out_w,
                             int out_h, int coeff_capacity,
                             short* out) {
  return DecodeClips(path, clip_starts, num_clips, consecutive, out_w,
                     out_h, reinterpret_cast<unsigned char*>(out),
                     kPixDct, coeff_capacity);
}

long long rnb_pool_submit_dct(void* pool, const char* path,
                              const long long* clip_starts,
                              int num_clips, int consecutive,
                              int out_w, int out_h, int coeff_capacity,
                              short* out) {
  if (!pool || num_clips < 0 || coeff_capacity < 1) return -1;
  Job job;
  job.path = path;
  job.starts.assign(clip_starts, clip_starts + num_clips);
  job.consecutive = consecutive;
  job.out_w = out_w;
  job.out_h = out_h;
  job.pixfmt = kPixDct;
  job.dct_capacity = coeff_capacity;
  job.out = reinterpret_cast<unsigned char*>(out);
  return static_cast<Pool*>(pool)->Submit(std::move(job));
}

int rnb_pool_wait(void* pool, long long ticket) {
  if (!pool || ticket <= 0) return kErrArg;
  return static_cast<Pool*>(pool)->Wait(ticket);
}

// 1 = done (result still pending retrieval via wait), 0 = in flight.
int rnb_pool_peek(void* pool, long long ticket) {
  if (!pool || ticket <= 0) return kErrArg;
  return static_cast<Pool*>(pool)->Peek(ticket) ? 1 : 0;
}

// Totals since the pool was made; a reader takes two and subtracts.
int rnb_pool_stats(void* pool, long long* busy_ns, long long* frames) {
  if (!pool) return kErrArg;
  Pool* p = static_cast<Pool*>(pool);
  if (busy_ns) *busy_ns = p->busy_ns.load();
  if (frames) *frames = p->frames_done.load();
  return 0;
}

}  // extern "C"
