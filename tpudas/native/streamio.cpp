// streamio: native ingest runtime for the tpudas edge path.
//
// The reference stack funnels every interrogator byte through
// libhdf5/pytables (reference lf_das.py:232 via DASCore's "dasdae"
// format). That is fine for archival, but the real-time loop's
// host-side hot cost is window assembly — read + merge of the
// overlap-save window before the device kernel runs (SURVEY.md §3.1
// hot loops #2/#3). This library provides the TPU-feed-rate
// alternative: a flat binary stream format ("tdas") an interrogator
// can append with O(1) framing, plus threaded range readers that
// convert (optionally int16-quantized) samples straight into the
// pinned float32 window buffer the device DMA consumes.
//
// Layout (little-endian):
//   0  : magic "TDAS"
//   4  : u32 version (=1)
//   8  : u64 t0_ns   epoch ns of first sample
//   16 : u64 dt_ns   sample interval ns
//   24 : u32 n_time
//   28 : u32 n_ch
//   32 : u32 dtype   0=float32, 1=int16 (scaled)
//   36 : f32 scale   physical = raw * scale (int16 only)
//   40 : f64 d0      first channel distance (m)
//   48 : f64 dx      channel spacing (m)
//   56 : u64 reserved
//   64 : payload, row-major (n_time, n_ch)
//
// All functions return 0 on success or a positive errno-style code.

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <unistd.h>

namespace {

constexpr uint32_t kMagic = 0x53414454;  // "TDAS" little-endian
constexpr uint32_t kVersion = 1;
constexpr size_t kHeaderSize = 64;
// a window chunk aims at this many output bytes, and takes at least
// kMinChunkRows rows; channel sub-spans are read through a bounce
// buffer of kBounceBytes
constexpr size_t kChunkBytes = size_t{16} << 20;
constexpr uint64_t kMinChunkRows = 2048;
constexpr size_t kBounceBytes = size_t{8} << 20;
constexpr size_t kPageBytes = 4096;  // the smallest page: touches hit every page

#pragma pack(push, 1)
struct TdasHeader {
  uint32_t magic;
  uint32_t version;
  uint64_t t0_ns;
  uint64_t dt_ns;
  uint32_t n_time;
  uint32_t n_ch;
  uint32_t dtype;  // 0=f32, 1=i16
  float scale;
  double d0;
  double dx;
  uint64_t reserved;
};
#pragma pack(pop)

static_assert(sizeof(TdasHeader) == kHeaderSize, "header must be 64 bytes");

size_t dtype_size(uint32_t dtype) { return dtype == 1 ? 2 : 4; }

int read_header_fd(int fd, TdasHeader* h) {
  ssize_t got = pread(fd, h, kHeaderSize, 0);
  if (got != static_cast<ssize_t>(kHeaderSize)) return EIO;
  if (h->magic != kMagic) return EINVAL;
  if (h->version != kVersion) return ENOTSUP;
  // known dtype codes only (0=f32, 1=i16): a corrupt/future file must
  // fail consistently with the python reader, not decode as f32 noise
  if (h->dtype != 0 && h->dtype != 1) return EINVAL;
  return 0;
}

int pread_full(int fd, void* dst, size_t bytes, off_t off) {
  size_t done = 0;
  while (done < bytes) {
    ssize_t got = pread(fd, static_cast<unsigned char*>(dst) + done,
                        bytes - done, off + static_cast<off_t>(done));
    if (got <= 0) return EIO;
    done += static_cast<size_t>(got);
  }
  return 0;
}

// Read rows [t_lo, t_hi) x channels [c_lo, c_hi) of one open file into
// out (row-major (t_hi-t_lo, c_hi-c_lo)). With `decode`, out is f32 and
// i16 payload is converted (* scale); without it, out keeps the
// payload dtype and only the channel slice is copied (the device-decode
// ingest path, where quantized int16 samples cross PCIe at half the
// float32 byte count and the TPU does the (cast * scale) decode).
// Full-width rows that need no conversion are one pread straight into
// out; otherwise IO is done in ~8 MB contiguous preads into `bounce`
// (one syscall per ~8 MB, not per row) and the channel sub-span is
// extracted in memory.
int read_rows(int fd, const TdasHeader& h, uint64_t t_lo, uint64_t t_hi,
              uint32_t c_lo, uint32_t c_hi, bool decode, unsigned char* out,
              std::vector<unsigned char>& bounce) {
  const size_t es = dtype_size(h.dtype);
  const size_t row_bytes = static_cast<size_t>(h.n_ch) * es;
  const size_t span_ch = c_hi - c_lo;
  const bool convert = decode && h.dtype == 1;
  if (c_lo == 0 && c_hi == h.n_ch && !convert) {
    const size_t bytes = (t_hi - t_lo) * row_bytes;
    // Fault the destination in from this thread before the read. A
    // pread into untouched pages faults them inside the kernel's copy,
    // which a sandboxed kernel such as gVisor serialises across
    // threads: on a gVisor TPU v5e host a 1.2 GB window read into fresh
    // pages took ~1 s on 1 to 12 threads, and 0.07 s into touched ones.
    volatile unsigned char* dst = out;
    for (size_t i = 0; i < bytes; i += kPageBytes) dst[i] = 0;
    if (bytes > 0) dst[bytes - 1] = 0;
    return pread_full(fd, out, bytes,
                      static_cast<off_t>(kHeaderSize + t_lo * row_bytes));
  }
  const size_t out_row_bytes = span_ch * (decode ? sizeof(float) : es);
  const size_t rows_per_read =
      std::max<size_t>(1, kBounceBytes / row_bytes);
  bounce.resize(rows_per_read * row_bytes);
  for (uint64_t t = t_lo; t < t_hi; t += rows_per_read) {
    const uint64_t n = std::min<uint64_t>(rows_per_read, t_hi - t);
    int rc = pread_full(fd, bounce.data(), n * row_bytes,
                        static_cast<off_t>(kHeaderSize + t * row_bytes));
    if (rc != 0) return rc;
    for (uint64_t r = 0; r < n; ++r) {
      const unsigned char* src =
          bounce.data() + r * row_bytes + static_cast<size_t>(c_lo) * es;
      unsigned char* orow = out + (t - t_lo + r) * out_row_bytes;
      if (convert) {
        const int16_t* raw = reinterpret_cast<const int16_t*>(src);
        float* o = reinterpret_cast<float*>(orow);
        for (size_t c = 0; c < span_ch; ++c)
          o[c] = static_cast<float>(raw[c]) * h.scale;
      } else {
        std::memcpy(orow, src, out_row_bytes);
      }
    }
  }
  return 0;
}

// One row chunk of a window: rows [lo, hi) of file `file`, landing at
// output row `out_row`.
struct Chunk {
  int file;
  uint64_t lo, hi, out_row;
};

// Cut each file segment [row_lo[i], row_hi[i]) into chunks of
// max(kMinChunkRows, kChunkBytes / out_row_bytes) rows, so the count
// follows the window's bytes: a chunk never crosses a segment, and
// only a segment's last chunk may be shorter.
std::vector<Chunk> plan_chunks(const uint64_t* row_lo, const uint64_t* row_hi,
                               const uint64_t* out_row0, int n_files,
                               size_t out_row_bytes) {
  const uint64_t rows = std::max<uint64_t>(
      kMinChunkRows, kChunkBytes / std::max<size_t>(1, out_row_bytes));
  std::vector<Chunk> chunks;
  for (int i = 0; i < n_files; ++i) {
    for (uint64_t lo = row_lo[i]; lo < row_hi[i]; lo += rows) {
      chunks.push_back({i, lo, std::min(row_hi[i], lo + rows),
                        out_row0[i] + (lo - row_lo[i])});
    }
  }
  return chunks;
}

// Multi-file window assembly: for file i, copy rows [row_lo[i],
// row_hi[i]) x ch [c_lo, c_hi) into out starting at output row
// out_row0[i]. The segments are cut into row chunks (plan_chunks) that
// min(n_threads, chunks) workers pull from one atomic counter, each
// reading straight into its chunk's output rows, so the reads and the
// first-touch page faults of a large window are spread over every
// worker, not one thread per file. Each worker opens its own fd per
// file it reads and checks the header: with `decode` any payload
// dtype (out is f32), without it `raw_dtype` only (out keeps it). The
// first nonzero code from any chunk is returned, after every worker
// has joined. chunks_out / threads_out, when given, receive the chunk
// and worker counts.
int assemble(const char* const* paths, const uint64_t* row_lo,
             const uint64_t* row_hi, const uint64_t* out_row0, int n_files,
             uint32_t c_lo, uint32_t c_hi, bool decode, uint32_t raw_dtype,
             unsigned char* out, int n_threads, int* chunks_out,
             int* threads_out) {
  if (n_files < 0 || (!decode && raw_dtype != 0 && raw_dtype != 1))
    return EINVAL;
  if (c_lo > c_hi) return ERANGE;
  for (int i = 0; i < n_files; ++i)
    if (row_lo[i] > row_hi[i]) return ERANGE;
  const size_t out_row_bytes = static_cast<size_t>(c_hi - c_lo) *
                               (decode ? sizeof(float) : dtype_size(raw_dtype));
  const std::vector<Chunk> chunks =
      plan_chunks(row_lo, row_hi, out_row0, n_files, out_row_bytes);
  const int n_workers = static_cast<int>(std::min<size_t>(
      static_cast<size_t>(std::max(n_threads, 1)), chunks.size()));
  if (chunks_out) *chunks_out = static_cast<int>(chunks.size());
  if (threads_out) *threads_out = n_workers;

  std::atomic<size_t> next{0};
  std::atomic<int> err{0};
  auto worker = [&]() {
    int fd = -1;
    int file = -1;
    TdasHeader h;
    std::vector<unsigned char> bounce;
    for (;;) {
      const size_t k = next.fetch_add(1);
      if (k >= chunks.size() || err.load() != 0) break;
      const Chunk& c = chunks[k];
      int rc = 0;
      if (c.file != file) {
        if (fd >= 0) close(fd);
        file = c.file;
        fd = open(paths[file], O_RDONLY);
        if (fd < 0) {
          rc = errno ? errno : EIO;
        } else {
          rc = read_header_fd(fd, &h);
          if (rc == 0 && !decode && h.dtype != raw_dtype) rc = EINVAL;
          if (rc == 0 && (row_hi[file] > h.n_time || c_hi > h.n_ch))
            rc = ERANGE;
        }
      }
      if (rc == 0)
        rc = read_rows(fd, h, c.lo, c.hi, c_lo, c_hi, decode,
                       out + c.out_row * out_row_bytes, bounce);
      if (rc != 0) {
        int none = 0;
        err.compare_exchange_strong(none, rc);
        break;
      }
    }
    if (fd >= 0) close(fd);
  };
  std::vector<std::thread> workers;
  for (int i = 1; i < n_workers; ++i) workers.emplace_back(worker);
  if (n_workers > 0) worker();
  for (auto& w : workers) w.join();
  return err.load();
}

}  // namespace

extern "C" {

int tdas_write(const char* path, uint64_t t0_ns, uint64_t dt_ns,
               uint32_t n_time, uint32_t n_ch, uint32_t dtype, float scale,
               double d0, double dx, const void* data) {
  TdasHeader h{};
  h.magic = kMagic;
  h.version = kVersion;
  h.t0_ns = t0_ns;
  h.dt_ns = dt_ns;
  h.n_time = n_time;
  h.n_ch = n_ch;
  h.dtype = dtype;
  h.scale = scale;
  h.d0 = d0;
  h.dx = dx;
  FILE* f = std::fopen(path, "wb");
  if (!f) return errno ? errno : EIO;
  const size_t payload =
      static_cast<size_t>(n_time) * n_ch * dtype_size(dtype);
  int rc = 0;
  if (std::fwrite(&h, 1, kHeaderSize, f) != kHeaderSize) rc = EIO;
  if (rc == 0 && std::fwrite(data, 1, payload, f) != payload) rc = EIO;
  if (std::fclose(f) != 0 && rc == 0) rc = EIO;
  return rc;
}

int tdas_read_header(const char* path, uint64_t* t0_ns, uint64_t* dt_ns,
                     uint32_t* n_time, uint32_t* n_ch, uint32_t* dtype,
                     float* scale, double* d0, double* dx) {
  int fd = open(path, O_RDONLY);
  if (fd < 0) return errno ? errno : EIO;
  TdasHeader h;
  int rc = read_header_fd(fd, &h);
  close(fd);
  if (rc != 0) return rc;
  *t0_ns = h.t0_ns;
  *dt_ns = h.dt_ns;
  *n_time = h.n_time;
  *n_ch = h.n_ch;
  *dtype = h.dtype;
  *scale = h.scale;
  *d0 = h.d0;
  *dx = h.dx;
  return 0;
}

// Threaded single-file block read: rows [t_lo, t_hi) x ch [c_lo, c_hi)
// into out (f32 row-major): a window of one segment.
int tdas_read_block(const char* path, uint64_t t_lo, uint64_t t_hi,
                    uint32_t c_lo, uint32_t c_hi, float* out,
                    int n_threads) {
  const uint64_t out_row0 = 0;
  return assemble(&path, &t_lo, &t_hi, &out_row0, 1, c_lo, c_hi, true, 0,
                  reinterpret_cast<unsigned char*>(out), n_threads, nullptr,
                  nullptr);
}

// Parallel multi-file window assembly into a float32 buffer, decoding
// int16 payload: the host half of the overlap-save window pipeline
// (see assemble for the segments, chunks and counts).
int tdas_assemble_window(const char** paths, const uint64_t* row_lo,
                         const uint64_t* row_hi, const uint64_t* out_row0,
                         int n_files, uint32_t c_lo, uint32_t c_hi,
                         float* out, int n_threads, int* chunks_out,
                         int* threads_out) {
  return assemble(paths, row_lo, row_hi, out_row0, n_files, c_lo, c_hi, true,
                  0, reinterpret_cast<unsigned char*>(out), n_threads,
                  chunks_out, threads_out);
}

// Raw (no-conversion) multi-file window assembly into a payload-dtype
// buffer: every file must carry `expect_dtype` or the call fails with
// EINVAL (the planner guarantees uniformity; this re-checks at the
// byte level).
int tdas_assemble_window_raw(const char** paths, const uint64_t* row_lo,
                             const uint64_t* row_hi,
                             const uint64_t* out_row0, int n_files,
                             uint32_t c_lo, uint32_t c_hi,
                             uint32_t expect_dtype, unsigned char* out,
                             int n_threads, int* chunks_out,
                             int* threads_out) {
  return assemble(paths, row_lo, row_hi, out_row0, n_files, c_lo, c_hi,
                  false, expect_dtype, out, n_threads, chunks_out,
                  threads_out);
}

// The row chunks the assemblers cut these segments into, for output
// rows of out_row_bytes: the first max_chunks as (file, lo, hi,
// out_row) quadruples into chunks; returns the total count.
int tdas_plan_window_chunks(const uint64_t* row_lo, const uint64_t* row_hi,
                            const uint64_t* out_row0, int n_files,
                            uint64_t out_row_bytes, uint64_t* chunks,
                            int max_chunks) {
  const std::vector<Chunk> plan =
      plan_chunks(row_lo, row_hi, out_row0, n_files, out_row_bytes);
  for (size_t k = 0; k < plan.size() && k < static_cast<size_t>(max_chunks);
       ++k) {
    chunks[4 * k] = static_cast<uint64_t>(plan[k].file);
    chunks[4 * k + 1] = plan[k].lo;
    chunks[4 * k + 2] = plan[k].hi;
    chunks[4 * k + 3] = plan[k].out_row;
  }
  return static_cast<int>(plan.size());
}

}  // extern "C"
