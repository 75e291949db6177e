// Native data pipeline for mxnet_tpu.
//
// Reference counterpart: src/io/iter_image_recordio.cc + iter_prefetcher.h +
// image_augmenter.h (+ dmlc InputSplit/RecordIO, OpenMP decode). This is the
// same architecture rebuilt for the TPU host: a pool of worker threads that
// read RecordIO-framed JPEG records, decode with libjpeg, augment
// (resize-short / crop / mirror / mean / scale) and assemble float32 NCHW
// or NHWC batches (NHWC is the TPU fast path and is also cheaper here:
// decoded pixels are already HWC), delivered in order through a bounded
// queue so the accelerator never waits on the input pipeline.
//
// File format (see mxnet_tpu/recordio.py, the python reference writer):
//   per record: u32 magic 'CREC' (0x54524543 LE), u32 crc32(payload),
//               u64 length, payload, zero-pad to 8 bytes.
//   payload (image records): u32 flag, f32 label, u64 id, u64 id2,
//               [flag>0: f32 label vector], image bytes (JPEG here).
//
// C ABI only; loaded from python via ctypes (no pybind11 in the image).

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

#include <jpeglib.h>
#include <setjmp.h>
#include <zlib.h>

namespace {

constexpr uint32_t kRecordMagic = 0x54524543;  // 'CREC'

struct RecordHeader {
  uint32_t magic;
  uint32_t crc;
  uint64_t length;
} __attribute__((packed));

struct IRHeader {
  uint32_t flag;
  float label;
  uint64_t id;
  uint64_t id2;
} __attribute__((packed));

// ---------------------------------------------------------------- JPEG decode
struct JpegErrorMgr {
  jpeg_error_mgr pub;
  jmp_buf jump;
};

void jpeg_error_exit(j_common_ptr cinfo) {
  JpegErrorMgr* err = reinterpret_cast<JpegErrorMgr*>(cinfo->err);
  longjmp(err->jump, 1);
}

// Decode JPEG bytes to HWC u8 RGB. Returns false on failure (non-JPEG etc).
bool DecodeJpeg(const uint8_t* buf, size_t len, std::vector<uint8_t>* out,
                int* height, int* width) {
  if (len < 2 || buf[0] != 0xFF || buf[1] != 0xD8) return false;  // not JPEG
  jpeg_decompress_struct cinfo;
  JpegErrorMgr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = jpeg_error_exit;
  if (setjmp(jerr.jump)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(buf), len);
  jpeg_read_header(&cinfo, TRUE);
  cinfo.out_color_space = JCS_RGB;
  jpeg_start_decompress(&cinfo);
  *height = cinfo.output_height;
  *width = cinfo.output_width;
  out->resize(size_t(*height) * *width * 3);
  while (cinfo.output_scanline < cinfo.output_height) {
    uint8_t* row = out->data() + size_t(cinfo.output_scanline) * *width * 3;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// Bilinear resize HWC u8 -> HWC u8. Fixed-point (16.16) with the x-axis
// taps/weights precomputed once per image instead of per row — the resize
// is the hottest non-decode stage of the pipeline, so it avoids all
// per-pixel float math and recomputation.
void ResizeBilinear(const uint8_t* src, int sh, int sw, uint8_t* dst, int dh,
                    int dw) {
  constexpr int kShift = 16;
  constexpr int64_t kOne = int64_t(1) << kShift;
  const int64_t ry = dh > 1 ? (int64_t(sh - 1) << kShift) / (dh - 1) : 0;
  const int64_t rx = dw > 1 ? (int64_t(sw - 1) << kShift) / (dw - 1) : 0;

  std::vector<int> x0s(dw), x1s(dw);
  std::vector<int64_t> wxs(dw);
  for (int x = 0; x < dw; ++x) {
    int64_t fx = x * rx;
    int x0 = int(fx >> kShift);
    x0s[x] = x0;
    x1s[x] = std::min(x0 + 1, sw - 1);
    wxs[x] = fx & (kOne - 1);
  }
  for (int y = 0; y < dh; ++y) {
    int64_t fy = y * ry;
    int y0 = int(fy >> kShift), y1 = std::min(y0 + 1, sh - 1);
    int64_t wy = fy & (kOne - 1);
    const uint8_t* r0 = src + size_t(y0) * sw * 3;
    const uint8_t* r1 = src + size_t(y1) * sw * 3;
    uint8_t* out = dst + size_t(y) * dw * 3;
    for (int x = 0; x < dw; ++x) {
      const uint8_t* p00 = r0 + x0s[x] * 3;
      const uint8_t* p01 = r0 + x1s[x] * 3;
      const uint8_t* p10 = r1 + x0s[x] * 3;
      const uint8_t* p11 = r1 + x1s[x] * 3;
      int64_t wx = wxs[x];
      for (int c = 0; c < 3; ++c) {
        // interpolate rows in x (<<16), then between rows in y (<<32);
        // 255 * 2^48 fits comfortably in int64
        int64_t top = p00[c] * (kOne - wx) + p01[c] * wx;
        int64_t bot = p10[c] * (kOne - wx) + p11[c] * wx;
        int64_t v = top * (kOne - wy) + bot * wy;
        out[x * 3 + c] = uint8_t((v + (int64_t(1) << 31)) >> 32);
      }
    }
  }
}

// ------------------------------------------------------------------- pipeline
struct PipelineConfig {
  int batch, channels, height, width, label_width;
  int rand_crop, rand_mirror, resize_short;
  float mean[3];
  int has_mean;
  float scale;
  // extended augmenters (reference image_augmenter.h / iter_normalize.h):
  // random resize-scale in [min_rscale, max_rscale]; per-dimension size
  // clamps (0 = off); photometric jitter out = (px - mean) * c + i with
  // c ~ U[1-max_contrast, 1+max_contrast], i ~ U[-max_illum, max_illum];
  // fixed mirror (vs the rand_mirror coin flip)
  float min_rscale, max_rscale;
  float min_img, max_img;
  float max_contrast, max_illum;
  int mirror;
  int shuffle;
  uint32_t seed;
  int num_threads, prefetch;
  int round_batch;
  int nhwc;    // emit [B,H,W,C] batches (TPU fast path) instead of [B,C,H,W]
  int out_u8;  // emit raw uint8 pixels (4x less host->device traffic; the
               // device normalizes) — requires mean/scale disabled
};

struct Batch {
  std::vector<float> data;     // when !out_u8
  std::vector<uint8_t> data8;  // when out_u8
  std::vector<float> labels;
  int pad;
};

class ImagePipeline {
 public:
  ImagePipeline(const char* path, const int64_t* offsets, int64_t n,
                const PipelineConfig& cfg)
      : cfg_(cfg), offsets_(offsets, offsets + n) {
    const char* skip = getenv("MXTPU_NATIVE_SKIP_DECODE");
    skip_decode_ = skip && skip[0] == '1';
    const char* skipw = getenv("MXTPU_NATIVE_SKIP_WORK");
    skip_work_ = skipw && skipw[0] == '1';
    fd_ = open(path, O_RDONLY);
    ok_ = fd_ >= 0;
    epoch_ = 0;
    StartEpoch();
  }

  ~ImagePipeline() {
    Shutdown();
    if (fd_ >= 0) close(fd_);
  }

  bool ok() const { return ok_; }

  // Pops the next in-order batch; returns 1 at epoch end, 0 on success,
  // negative on error. ``data_out`` is float* or uint8* per cfg.out_u8.
  int Next(void* data_out, float* label_out, int* pad_out) {
    std::unique_lock<std::mutex> lk(mu_);
    if (deliver_next_ >= tickets_total_) return 1;
    cv_ready_.wait(lk, [&] { return ready_.count(deliver_next_) || failed_; });
    if (failed_) return -1;
    Batch b = std::move(ready_[deliver_next_]);
    ready_.erase(deliver_next_);
    ++deliver_next_;
    cv_space_.notify_all();
    lk.unlock();
    if (cfg_.out_u8)
      std::memcpy(data_out, b.data8.data(), b.data8.size());
    else
      std::memcpy(data_out, b.data.data(), b.data.size() * sizeof(float));
    std::memcpy(label_out, b.labels.data(), b.labels.size() * sizeof(float));
    *pad_out = b.pad;
    return 0;
  }

  void Reset() {
    Shutdown();
    ++epoch_;
    StartEpoch();
  }

  int64_t BatchesPerEpoch() const { return tickets_total_; }

 private:
  void StartEpoch() {
    order_.resize(offsets_.size());
    for (size_t i = 0; i < order_.size(); ++i) order_[i] = i;
    if (cfg_.shuffle) {
      std::mt19937 rng(cfg_.seed + epoch_);
      std::shuffle(order_.begin(), order_.end(), rng);
    }
    int64_t n = order_.size();
    tickets_total_ =
        cfg_.round_batch ? (n + cfg_.batch - 1) / cfg_.batch : n / cfg_.batch;
    ticket_counter_ = 0;
    deliver_next_ = 0;
    failed_ = false;
    stop_ = false;
    ready_.clear();
    int nthreads = std::max(1, cfg_.num_threads);
    for (int i = 0; i < nthreads; ++i)
      workers_.emplace_back(&ImagePipeline::WorkerLoop, this, i);
  }

  void Shutdown() {
    {
      std::lock_guard<std::mutex> lk(mu_);
      stop_ = true;
      cv_space_.notify_all();
      cv_ready_.notify_all();
    }
    for (auto& t : workers_) t.join();
    workers_.clear();
  }

  void WorkerLoop(int wid) {
    std::mt19937 rng(cfg_.seed * 9973 + epoch_ * 131 + wid);
    while (true) {
      int64_t ticket = ticket_counter_.fetch_add(1);
      if (ticket >= tickets_total_) return;
      // bounded prefetch: don't run ahead of the consumer
      {
        std::unique_lock<std::mutex> lk(mu_);
        cv_space_.wait(lk, [&] {
          return stop_ || ticket < deliver_next_ + cfg_.prefetch;
        });
        if (stop_) return;
      }
      Batch b;
      if (!ProduceBatch(ticket, &rng, &b)) {
        std::lock_guard<std::mutex> lk(mu_);
        failed_ = true;
        cv_ready_.notify_all();
        return;
      }
      std::lock_guard<std::mutex> lk(mu_);
      ready_.emplace(ticket, std::move(b));
      cv_ready_.notify_all();
    }
  }

  bool ReadRecord(int64_t offset, std::vector<uint8_t>* payload) {
    RecordHeader hdr;
    if (pread(fd_, &hdr, sizeof(hdr), offset) != sizeof(hdr)) return false;
    if (hdr.magic != kRecordMagic) return false;
    payload->resize(hdr.length);
    ssize_t got = pread(fd_, payload->data(), hdr.length, offset + sizeof(hdr));
    if (got != ssize_t(hdr.length)) return false;
    uint32_t crc = crc32(0, payload->data(), hdr.length);
    return crc == hdr.crc;
  }

  bool ProduceBatch(int64_t ticket, std::mt19937* rng, Batch* out) {
    const int B = cfg_.batch, C = cfg_.channels, H = cfg_.height,
              W = cfg_.width;
    if (cfg_.out_u8)
      out->data8.assign(size_t(B) * C * H * W, 0);
    else
      out->data.assign(size_t(B) * C * H * W, 0.f);
    out->labels.assign(size_t(B) * cfg_.label_width, 0.f);
    int64_t n = order_.size();
    int64_t start = ticket * B;
    out->pad = int(std::max<int64_t>(0, start + B - n));
    if (skip_work_) return true;  // MXTPU_NATIVE_SKIP_WORK=1: deliver zeroed
    // batches, leaving only the serial path live (ticketing + ordered
    // delivery memcpy in Next())
    std::vector<uint8_t> payload, pixels, resized;
    for (int i = 0; i < B; ++i) {
      int64_t idx = order_[(start + i) % n];
      if (!ReadRecord(offsets_[idx], &payload)) return false;
      if (payload.size() < sizeof(IRHeader)) return false;
      IRHeader ir;
      std::memcpy(&ir, payload.data(), sizeof(ir));
      const uint8_t* img = payload.data() + sizeof(ir);
      size_t img_len = payload.size() - sizeof(ir);
      float* label_dst = out->labels.data() + size_t(i) * cfg_.label_width;
      if (ir.flag > 0) {
        size_t lbytes = size_t(ir.flag) * sizeof(float);
        if (img_len < lbytes) return false;
        std::memcpy(label_dst, img,
                    sizeof(float) * std::min<int>(ir.flag, cfg_.label_width));
        img += lbytes;
        img_len -= lbytes;
      } else {
        label_dst[0] = ir.label;
      }
      int h, w;
      if (skip_decode_) {
        // Debug mode (MXTPU_NATIVE_SKIP_DECODE=1): substitute the JPEG
        // decode with a constant-fill of the same nominal geometry, keeping
        // every other stage (record read, CRC, resize, crop, mirror, batch
        // assembly, delivery) live: what is left is the pipeline's
        // non-decode cost.
        h = w = std::max({256, cfg_.height, cfg_.width});
        pixels.assign(size_t(h) * w * 3, img_len ? img[0] : 0);
      } else if (!DecodeJpeg(img, img_len, &pixels, &h, &w)) {
        return false;
      }
      const uint8_t* hwc = pixels.data();
      // resize so the short side is resize_short (or to fit the crop),
      // jittered by the random scale factor and clamped to the img-size
      // bounds; the result stays crop-feasible (>= data_shape)
      float rscale = 1.f;
      if (cfg_.min_rscale != 1.f || cfg_.max_rscale != 1.f) {
        float u = float((*rng)()) * (1.f / 4294967296.f);
        rscale = cfg_.min_rscale + u * (cfg_.max_rscale - cfg_.min_rscale);
      }
      int target_short = cfg_.resize_short;
      if (h < H || w < W || target_short > 0 || rscale != 1.f ||
          cfg_.min_img > 0.f || cfg_.max_img > 0.f) {
        int short_side = std::min(h, w);
        float s = target_short > 0 ? float(target_short) / short_side : 1.f;
        s *= rscale;
        float fnh = h * s, fnw = w * s;
        if (cfg_.min_img > 0.f) {
          fnh = std::max(fnh, cfg_.min_img);
          fnw = std::max(fnw, cfg_.min_img);
        }
        if (cfg_.max_img > 0.f) {
          fnh = std::min(fnh, cfg_.max_img);
          fnw = std::min(fnw, cfg_.max_img);
        }
        int nh = std::max(H, int(fnh + 0.5f));
        int nw = std::max(W, int(fnw + 0.5f));
        if (nh != h || nw != w) {  // identity resize (already at target
          resized.resize(size_t(nh) * nw * 3);  // short side) is a no-op
          ResizeBilinear(pixels.data(), h, w, resized.data(), nh, nw);
          hwc = resized.data();
          h = nh;
          w = nw;
        }
      }
      int top, left;
      if (cfg_.rand_crop) {
        top = int((*rng)() % uint32_t(h - H + 1));
        left = int((*rng)() % uint32_t(w - W + 1));
      } else {
        top = (h - H) / 2;
        left = (w - W) / 2;
      }
      bool mirror = cfg_.rand_mirror && ((*rng)() & 1u);
      if (cfg_.mirror) mirror = true;
      float con = 1.f, ill = 0.f;
      if (!cfg_.out_u8 && (cfg_.max_contrast > 0.f || cfg_.max_illum > 0.f)) {
        float u1 = float((*rng)()) * (1.f / 4294967296.f);
        float u2 = float((*rng)()) * (1.f / 4294967296.f);
        con = 1.f + (u1 * 2.f - 1.f) * cfg_.max_contrast;
        ill = (u2 * 2.f - 1.f) * cfg_.max_illum;
      }
      const bool nhwc = cfg_.nhwc != 0;
      float* dst = cfg_.out_u8 ? nullptr
                               : out->data.data() + size_t(i) * C * H * W;
      uint8_t* dst8 = cfg_.out_u8
                          ? out->data8.data() + size_t(i) * C * H * W
                          : nullptr;
      for (int y = 0; y < H; ++y) {
        for (int x = 0; x < W; ++x) {
          int sx = mirror ? (W - 1 - x) : x;
          const uint8_t* px =
              hwc + (size_t(top + y) * w + (left + sx)) * 3;
          for (int c = 0; c < C && c < 3; ++c) {
            size_t at = nhwc ? (size_t(y) * W + x) * C + c
                             : (size_t(c) * H + y) * W + x;
            if (dst8) {
              dst8[at] = px[c];
            } else {
              float v = float(px[c]);
              if (cfg_.has_mean) v -= cfg_.mean[c];
              dst[at] = (v * con + ill) * cfg_.scale;
            }
          }
        }
      }
    }
    return true;
  }

  PipelineConfig cfg_;
  std::vector<int64_t> offsets_;
  std::vector<int64_t> order_;
  int fd_ = -1;
  bool ok_ = false;
  bool skip_decode_ = false;
  bool skip_work_ = false;
  int epoch_ = 0;

  std::mutex mu_;
  std::condition_variable cv_ready_, cv_space_;
  std::map<int64_t, Batch> ready_;
  std::atomic<int64_t> ticket_counter_{0};
  int64_t tickets_total_ = 0;
  int64_t deliver_next_ = 0;
  bool failed_ = false;
  bool stop_ = false;
  std::vector<std::thread> workers_;
};

}  // namespace

// ------------------------------------------------------------------- C ABI
extern "C" {

// Scan record offsets in a CREC file. Returns count (<= cap), or -1 on error.
int64_t mxtpu_scan_offsets(const char* path, int64_t* out, int64_t cap) {
  FILE* f = fopen(path, "rb");
  if (!f) return -1;
  int64_t count = 0;
  int64_t pos = 0;
  RecordHeader hdr;
  while (fread(&hdr, sizeof(hdr), 1, f) == 1) {
    if (hdr.magic != kRecordMagic) {
      fclose(f);
      return -1;
    }
    if (count < cap) out[count] = pos;
    ++count;
    int64_t padded = (hdr.length + 7) & ~int64_t(7);
    pos += sizeof(hdr) + padded;
    if (fseek(f, pos, SEEK_SET) != 0) break;
  }
  fclose(f);
  return count;
}

void* mxtpu_pipeline_create(const char* path, const int64_t* offsets,
                            int64_t n_offsets, int batch, int channels,
                            int height, int width, int label_width,
                            int rand_crop, int rand_mirror, int resize_short,
                            const float* mean3, float scale, int shuffle,
                            uint32_t seed, int num_threads, int prefetch,
                            int round_batch, int nhwc, int out_u8,
                            const float* aug6, int mirror) {
  // aug6 (nullable): {min_random_scale, max_random_scale, min_img_size,
  // max_img_size, max_random_contrast, max_random_illumination}
  PipelineConfig cfg;
  cfg.batch = batch;
  cfg.channels = channels;
  cfg.height = height;
  cfg.width = width;
  cfg.label_width = label_width;
  cfg.rand_crop = rand_crop;
  cfg.rand_mirror = rand_mirror;
  cfg.resize_short = resize_short;
  cfg.has_mean = mean3 != nullptr;
  if (mean3) std::memcpy(cfg.mean, mean3, sizeof(cfg.mean));
  cfg.scale = scale;
  cfg.shuffle = shuffle;
  cfg.seed = seed;
  cfg.num_threads = num_threads;
  cfg.prefetch = std::max(1, prefetch);
  cfg.round_batch = round_batch;
  cfg.nhwc = nhwc;
  cfg.out_u8 = out_u8;
  cfg.min_rscale = aug6 ? aug6[0] : 1.f;
  cfg.max_rscale = aug6 ? aug6[1] : 1.f;
  cfg.min_img = aug6 ? aug6[2] : 0.f;
  cfg.max_img = aug6 ? aug6[3] : 0.f;
  cfg.max_contrast = aug6 ? aug6[4] : 0.f;
  cfg.max_illum = aug6 ? aug6[5] : 0.f;
  cfg.mirror = mirror;
  auto* p = new ImagePipeline(path, offsets, n_offsets, cfg);
  if (!p->ok()) {
    delete p;
    return nullptr;
  }
  return p;
}

int mxtpu_pipeline_next(void* handle, void* data_out, float* label_out,
                        int* pad_out) {
  return static_cast<ImagePipeline*>(handle)->Next(data_out, label_out,
                                                   pad_out);
}

void mxtpu_pipeline_reset(void* handle) {
  static_cast<ImagePipeline*>(handle)->Reset();
}

int64_t mxtpu_pipeline_batches(void* handle) {
  return static_cast<ImagePipeline*>(handle)->BatchesPerEpoch();
}

void mxtpu_pipeline_destroy(void* handle) {
  delete static_cast<ImagePipeline*>(handle);
}

}  // extern "C"
