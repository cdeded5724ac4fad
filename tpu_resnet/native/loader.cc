// Native data-plane for tpu_resnet — the first-party replacement for the
// role TF's C++ tf.data stack played in the reference (SURVEY.md §2.4):
// FixedLengthRecordDataset (CIFAR bins, reference cifar_input.py:58) and
// TFRecordDataset framing + CRC32C verification (ImageNet shards,
// reference resnet_imagenet_train.py:169-183).
//
// Exposed as a C ABI consumed via ctypes (no pybind11 in the image).
// Threaded file reads matter here: the host side of the input pipeline is
// the one part of the framework where Python overhead is measurable.

#include <algorithm>
#include <cmath>
#include <csetjmp>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

#ifdef TR_WITH_JPEG
#include <jpeglib.h>
#endif

namespace {

// ----------------------------------------------------------- CRC32C (sw)
// Castagnoli polynomial, byte-table implementation; table generated at
// first use. (Matches tpu_resnet/data/tfrecord.py crc32c.)
uint32_t g_table[8][256];
bool g_table_init = false;

void init_table() {
  if (g_table_init) return;
  const uint32_t poly = 0x82F63B78u;
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t crc = i;
    for (int j = 0; j < 8; j++) crc = (crc >> 1) ^ ((crc & 1) ? poly : 0);
    g_table[0][i] = crc;
  }
  // Slice-by-8 tables for speed.
  for (int t = 1; t < 8; t++) {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = g_table[t - 1][i];
      g_table[t][i] = (c >> 8) ^ g_table[0][c & 0xFF];
    }
  }
  g_table_init = true;
}

uint32_t crc32c(const uint8_t* data, size_t n) {
  init_table();
  uint32_t crc = 0xFFFFFFFFu;
  size_t i = 0;
  // slice-by-8
  for (; i + 8 <= n; i += 8) {
    crc ^= (uint32_t)data[i] | ((uint32_t)data[i + 1] << 8) |
           ((uint32_t)data[i + 2] << 16) | ((uint32_t)data[i + 3] << 24);
    uint32_t hi = (uint32_t)data[i + 4] | ((uint32_t)data[i + 5] << 8) |
                  ((uint32_t)data[i + 6] << 16) | ((uint32_t)data[i + 7] << 24);
    crc = g_table[7][crc & 0xFF] ^ g_table[6][(crc >> 8) & 0xFF] ^
          g_table[5][(crc >> 16) & 0xFF] ^ g_table[4][(crc >> 24) & 0xFF] ^
          g_table[3][hi & 0xFF] ^ g_table[2][(hi >> 8) & 0xFF] ^
          g_table[1][(hi >> 16) & 0xFF] ^ g_table[0][(hi >> 24) & 0xFF];
  }
  for (; i < n; i++) crc = (crc >> 8) ^ g_table[0][(crc ^ data[i]) & 0xFF];
  return crc ^ 0xFFFFFFFFu;
}

inline uint32_t masked_crc(const uint8_t* data, size_t n) {
  uint32_t c = crc32c(data, n);
  return ((c >> 15) | (c << 17)) + 0xA282EAD8u;
}

int64_t file_size(const char* path) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return -1;
  std::fseek(f, 0, SEEK_END);
  int64_t n = std::ftell(f);
  std::fclose(f);
  return n;
}

}  // namespace

extern "C" {

// crc32c of a buffer (exposed for tests / cross-checking).
uint32_t tr_crc32c(const uint8_t* data, int64_t n) {
  return crc32c(data, (size_t)n);
}

// Read one whole file into out (caller sized it via tr_file_size).
// Returns bytes read or -1.
int64_t tr_file_size(const char* path) { return file_size(path); }

int64_t tr_read_file(const char* path, uint8_t* out, int64_t cap) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return -1;
  int64_t n = (int64_t)std::fread(out, 1, (size_t)cap, f);
  std::fclose(f);
  return n;
}

// Read many fixed-length-record files concurrently into one buffer laid
// out back-to-back in argument order. sizes[i] must equal the file size.
// Returns 0 on success, -(i+1) if file i failed.
int64_t tr_read_files_concat(const char** paths, const int64_t* sizes,
                             int64_t n_files, uint8_t* out,
                             int64_t num_threads) {
  std::vector<int64_t> offsets(n_files + 1, 0);
  for (int64_t i = 0; i < n_files; i++)
    offsets[i + 1] = offsets[i] + sizes[i];
  std::vector<int64_t> status(n_files, 0);
  int64_t nt = num_threads < 1 ? 1 : num_threads;
  std::vector<std::thread> threads;
  for (int64_t t = 0; t < nt; t++) {
    threads.emplace_back([&, t]() {
      for (int64_t i = t; i < n_files; i += nt) {
        int64_t got = tr_read_file(paths[i], out + offsets[i], sizes[i]);
        if (got != sizes[i]) status[i] = -(i + 1);
      }
    });
  }
  for (auto& th : threads) th.join();
  for (int64_t i = 0; i < n_files; i++)
    if (status[i]) return status[i];
  return 0;
}

// Split a TFRecord file already loaded at `buf` into records.
// Writes (offset, length) pairs into out_spans (capacity max_records).
// verify: 0 = none, 1 = verify both CRCs.
// Returns record count, or -1 on framing error, -2 on CRC mismatch,
// -3 if more than max_records.
int64_t tr_tfrecord_split(const uint8_t* buf, int64_t n, int64_t* out_spans,
                          int64_t max_records, int32_t verify) {
  int64_t pos = 0, count = 0;
  while (pos < n) {
    if (pos + 12 > n) return -1;
    uint64_t len;
    std::memcpy(&len, buf + pos, 8);  // little-endian hosts only (x86/arm)
    if (verify) {
      uint32_t want;
      std::memcpy(&want, buf + pos + 8, 4);
      if (masked_crc(buf + pos, 8) != want) return -2;
    }
    int64_t data_off = pos + 12;
    if (data_off + (int64_t)len + 4 > n) return -1;
    if (verify) {
      uint32_t want;
      std::memcpy(&want, buf + data_off + len, 4);
      if (masked_crc(buf + data_off, len) != want) return -2;
    }
    if (count >= max_records) return -3;
    out_spans[2 * count] = data_off;
    out_spans[2 * count + 1] = (int64_t)len;
    count++;
    pos = data_off + (int64_t)len + 4;
  }
  return count;
}

// ------------------------------------------------------ JPEG (VGG host half)
// The C++ replacement for the reference's tf.image.decode_image + slim VGG
// resize/crop host work (reference resnet_imagenet_train.py:142-152,
// vgg_preprocessing.py:259-314). Decode + aspect-preserving bilinear resize
// (shorter side = resize_side, using libjpeg DCT 1/2^k prescaling when it
// keeps the shorter side above target) + crop. Called from Python worker
// threads via ctypes, which releases the GIL — so decode scales across
// cores where PIL mostly serializes.

// 0 = built without libjpeg, 1 = plain libjpeg, 2 = libjpeg-turbo
// partial decode (crop/skip).
int32_t tr_has_jpeg(void) {
#if defined(TR_WITH_JPEG) && defined(TR_TURBO_CROP)
  return 2;
#elif defined(TR_WITH_JPEG)
  return 1;
#else
  return 0;
#endif
}

#ifdef TR_WITH_JPEG
namespace {

struct JpegErr {
  jpeg_error_mgr mgr;
  jmp_buf jb;
};

void jpeg_err_exit(j_common_ptr cinfo) {
  longjmp(reinterpret_cast<JpegErr*>(cinfo->err)->jb, 1);
}

// One decompress struct per thread, reused across images (create/destroy
// per image costs allocator round-trips; the iterator decodes millions).
// An error longjmp destroys it and the next call recreates.
struct TlDecoder {
  jpeg_decompress_struct cinfo;
  JpegErr err;
  bool init = false;

  jpeg_decompress_struct* get() {
    if (!init) {
      cinfo.err = jpeg_std_error(&err.mgr);
      err.mgr.error_exit = jpeg_err_exit;
      jpeg_create_decompress(&cinfo);
      init = true;
    }
    return &cinfo;
  }
  void fail() {  // called after longjmp: struct state is undefined
    jpeg_destroy_decompress(&cinfo);
    init = false;
  }
  ~TlDecoder() {
    if (init) jpeg_destroy_decompress(&cinfo);
  }
};

thread_local TlDecoder g_decoder;

// Separable triangle-filter resize (support scaled by the downscale
// factor — antialiased like PIL's BILINEAR, unlike 2-tap sampling) for
// RGB uint8.
struct ResampleAxis {
  std::vector<int> first;      // per-output-pixel first source index
  std::vector<int> count;      // taps per output pixel
  std::vector<float> weights;  // ksize-strided normalized weights
  int ksize;
};

void precompute_axis(int in, int out, ResampleAxis& ax) {
  const double scale = (double)in / out;
  const double filterscale = std::max(scale, 1.0);
  const double support = filterscale;  // triangle filter radius 1
  ax.ksize = (int)std::ceil(support) * 2 + 1;
  ax.first.resize(out);
  ax.count.resize(out);
  ax.weights.assign((size_t)out * ax.ksize, 0.f);
  for (int i = 0; i < out; i++) {
    const double center = (i + 0.5) * scale;
    int xmin = (int)(center - support + 0.5);
    if (xmin < 0) xmin = 0;
    int xmax = (int)(center + support + 0.5);
    if (xmax > in) xmax = in;
    double total = 0.0;
    float* w = &ax.weights[(size_t)i * ax.ksize];
    for (int x = xmin; x < xmax; x++) {
      double t = std::abs((x + 0.5 - center) / filterscale);
      double v = t < 1.0 ? 1.0 - t : 0.0;
      w[x - xmin] = (float)v;
      total += v;
    }
    if (total > 0)
      for (int k = 0; k < xmax - xmin; k++) w[k] = (float)(w[k] / total);
    ax.first[i] = xmin;
    ax.count[i] = xmax - xmin;
  }
}

// Window-restricted resize: computes ONLY the output pixels
// [x0, x0+cw) × [y0, y0+ch) of the virtual dw×dh resized image — the
// weights are per-output-index, so the window's pixels are bit-identical
// to a full resize followed by a crop, at a fraction of the work (the
// crop is 224² out of up to 512²·aspect). ``src`` holds source columns
// [src_x_off, src_x_off+w_buf) of rows [src_y_off, …) — the decoder only
// materializes the span the window's taps touch.
void resize_bilinear_window(const uint8_t* src, int w_full, int h_full,
                            int w_buf, int src_x_off, int src_y_off,
                            int dw, int dh, int x0, int y0, int cw, int ch,
                            uint8_t* dst) {
  ResampleAxis hx, vx;
  precompute_axis(w_full, dw, hx);
  precompute_axis(h_full, dh, vx);
  int row_lo = vx.first[y0], row_hi = 0;
  for (int y = y0; y < y0 + ch; y++)
    row_hi = std::max(row_hi, vx.first[y] + vx.count[y]);
  // Horizontal pass into a float intermediate over just the needed rows
  // and the cw output columns.
  std::vector<float> tmp((size_t)(row_hi - row_lo) * cw * 3);
  for (int y = row_lo; y < row_hi; y++) {
    const uint8_t* row = src + (size_t)(y - src_y_off) * w_buf * 3;
    float* orow = tmp.data() + (size_t)(y - row_lo) * cw * 3;
    for (int x = 0; x < cw; x++) {
      const float* wt = &hx.weights[(size_t)(x0 + x) * hx.ksize];
      const uint8_t* p = row + 3 * (hx.first[x0 + x] - src_x_off);
      float r = 0, g = 0, b = 0;
      for (int k = 0; k < hx.count[x0 + x]; k++, p += 3) {
        r += wt[k] * p[0];
        g += wt[k] * p[1];
        b += wt[k] * p[2];
      }
      orow[3 * x] = r;
      orow[3 * x + 1] = g;
      orow[3 * x + 2] = b;
    }
  }
  // Vertical pass straight into the crop output.
  for (int y = 0; y < ch; y++) {
    const float* wt = &vx.weights[(size_t)(y0 + y) * vx.ksize];
    uint8_t* orow = dst + (size_t)y * cw * 3;
    const float* base =
        tmp.data() + (size_t)(vx.first[y0 + y] - row_lo) * cw * 3;
    for (int x = 0; x < cw * 3; x++) {
      float v = 0;
      const float* col = base + x;
      for (int k = 0; k < vx.count[y0 + y]; k++, col += (size_t)cw * 3)
        v += wt[k] * *col;
      orow[x] = (uint8_t)std::min(255.f, std::max(0.f, v + 0.5f));
    }
  }
}

// Source-pixel span an output window's taps touch along one axis (for
// decode-time row/column cropping) — recomputes the axis cheaply; decode
// dominates. Must stay in lockstep with precompute_axis (the same
// first/count arrays drive resize_bilinear_window's reads).
void window_src_span(int in_full, int out_full, int o0, int n, int* lo,
                     int* hi) {
  ResampleAxis ax;
  precompute_axis(in_full, out_full, ax);
  *lo = ax.first[o0];
  int h = 0;
  for (int o = o0; o < o0 + n; o++)
    h = std::max(h, ax.first[o] + ax.count[o]);
  *hi = h;
}

}  // namespace
#endif  // TR_WITH_JPEG

// JPEG bytes → uint8 RGB [crop, crop, 3] written to out:
// aspect-preserving resize so the shorter side == resize_side, then a
// crop. fx/fy in [0,1) map uniformly onto the w-crop+1 valid offsets
// (each offset equal-weighted, like the reference's uniform random crop,
// vgg_preprocessing.py:88-168); fx/fy < 0 = floor-central crop
// ((w-crop)/2, vgg_preprocessing.py:171-193).
// Returns 0 on success; -1 decode error (caller falls back to PIL),
// -2 unsupported colorspace, -3 image smaller than the crop, -4 built
// without libjpeg.
int32_t tr_decode_jpeg_vgg(const uint8_t* jpeg, int64_t len,
                           int32_t resize_side, int32_t crop, float fx,
                           float fy, uint8_t* out) {
#ifndef TR_WITH_JPEG
  (void)jpeg; (void)len; (void)resize_side; (void)crop; (void)fx; (void)fy;
  (void)out;
  return -4;
#else
  jpeg_decompress_struct* cinfo = g_decoder.get();
  std::vector<uint8_t> decoded;
  if (setjmp(g_decoder.err.jb)) {
    g_decoder.fail();
    return -1;
  }
  jpeg_mem_src(cinfo, const_cast<uint8_t*>(jpeg), (unsigned long)len);
  if (jpeg_read_header(cinfo, TRUE) != JPEG_HEADER_OK) {
    jpeg_abort_decompress(cinfo);
    return -1;
  }
  if (cinfo->jpeg_color_space == JCS_CMYK ||
      cinfo->jpeg_color_space == JCS_YCCK) {
    jpeg_abort_decompress(cinfo);
    return -2;  // rare; PIL fallback handles these
  }
  cinfo->out_color_space = JCS_RGB;
  // DCT prescale: biggest 1/2^k that keeps the shorter side >= target.
  int denom = 1;
  while (denom < 8 &&
         (int)std::min(cinfo->image_width, cinfo->image_height) /
                 (denom * 2) >=
             resize_side)
    denom *= 2;
  cinfo->scale_num = 1;
  cinfo->scale_denom = denom;
  jpeg_start_decompress(cinfo);
  const int w = cinfo->output_width, h = cinfo->output_height;
  if (w < 1 || h < 1 || cinfo->output_components != 3) {
    int rc = cinfo->output_components != 3 ? -2 : -3;
    jpeg_abort_decompress(cinfo);
    return rc;
  }

  // Virtual resized dims (shorter side -> resize_side; round the other,
  // matching PIL-path semantics in data/imagenet.py::_resize_keep_aspect)
  // and the crop offsets — known BEFORE decoding, so only the source
  // window the crop's filter taps touch needs decoding + resizing.
  const float scale = (float)resize_side / std::min(w, h);
  const int rw = std::max(1, (int)std::lround(w * scale));
  const int rh = std::max(1, (int)std::lround(h * scale));
  if (rw < crop || rh < crop) {
    jpeg_abort_decompress(cinfo);
    return -3;
  }
  const int x0 = fx < 0 ? (rw - crop) / 2
                        : std::min((int)(fx * (rw - crop + 1)), rw - crop);
  const int y0 = fy < 0 ? (rh - crop) / 2
                        : std::min((int)(fy * (rh - crop + 1)), rh - crop);
  int col_lo, col_hi, row_lo, row_hi;
  window_src_span(w, rw, x0, crop, &col_lo, &col_hi);
  window_src_span(h, rh, y0, crop, &row_lo, &row_hi);

  int src_x_off = 0, w_buf = w;
#ifdef TR_TURBO_CROP
  // libjpeg-turbo partial decode: only the iMCU-aligned column span the
  // window needs is dequantized/IDCT'd, and rows outside [row_lo, row_hi)
  // are skipped (huffman-parsed only).
  {
    // Pad the requested span: fancy chroma upsampling reads neighbor
    // samples, so pixels at the decode boundary can differ from a full
    // decode — keep the boundary >= 8 px away from any pixel we use.
    const int pad = 8;
    int lo = std::max(0, col_lo - pad);
    JDIMENSION xoff = (JDIMENSION)lo;
    JDIMENSION xw = (JDIMENSION)(std::min(w, col_hi + pad) - lo);
    jpeg_crop_scanline(cinfo, &xoff, &xw);
    src_x_off = (int)xoff;
    w_buf = (int)cinfo->output_width;
    row_lo = std::max(0, row_lo - pad);
    row_hi = std::min(h, row_hi + pad);
  }
  while ((int)cinfo->output_scanline < row_lo)
    jpeg_skip_scanlines(
        cinfo, (JDIMENSION)(row_lo - (int)cinfo->output_scanline));
#else
  row_lo = 0;  // must decode from the top without skip support
#endif
  decoded.resize((size_t)(row_hi - row_lo) * w_buf * 3);
  while ((int)cinfo->output_scanline < row_hi) {
    uint8_t* row = decoded.data() +
                   (size_t)((int)cinfo->output_scanline - row_lo) * w_buf * 3;
    jpeg_read_scanlines(cinfo, &row, 1);
  }
  // Abort rather than finish: rows below the window are never decoded and
  // the (reused) struct returns to the ready state.
  jpeg_abort_decompress(cinfo);

  resize_bilinear_window(decoded.data(), w, h, w_buf, src_x_off, row_lo, rw,
                         rh, x0, y0, crop, crop, out);
  return 0;
#endif  // TR_WITH_JPEG
}

}  // extern "C"
