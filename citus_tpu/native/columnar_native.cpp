// Native columnar runtime: the IO + codec hot path.
//
// The reference implements its columnar engine in C inside PostgreSQL
// (src/backend/columnar/columnar_compression.c, columnar_reader.c);
// this library is the equivalent native layer under the Python/JAX
// planner: batch chunk reads (one pread per stream), zstd/lz4/zlib
// decompression, and validity-bitmap unpacking, all without the
// per-chunk Python overhead.  Exposed through a plain C ABI consumed
// via ctypes (no pybind11 dependency).

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <unistd.h>
#include <zstd.h>
#include <zlib.h>

extern "C" {
// liblz4 runtime is present; header is not — declare what we use.
int LZ4_decompress_safe(const char* src, char* dst, int srcSize, int dstCapacity);
int LZ4_compress_default(const char* src, char* dst, int srcSize, int dstCapacity);
int LZ4_compressBound(int inputSize);
}

enum Codec : int32_t {
    CODEC_NONE = 0,
    CODEC_ZSTD = 1,
    CODEC_LZ4 = 2,
    CODEC_ZLIB = 3,
};

extern "C" {

// ---- single-shot codecs -------------------------------------------------

// returns decompressed size, or -1 on failure
int64_t ct_decompress(int32_t codec, const uint8_t* src, int64_t src_len,
                      uint8_t* dst, int64_t dst_cap) {
    switch (codec) {
        case CODEC_NONE:
            if (src_len > dst_cap) return -1;
            memcpy(dst, src, (size_t)src_len);
            return src_len;
        case CODEC_ZSTD: {
            size_t n = ZSTD_decompress(dst, (size_t)dst_cap, src, (size_t)src_len);
            if (ZSTD_isError(n)) return -1;
            return (int64_t)n;
        }
        case CODEC_LZ4: {
            int n = LZ4_decompress_safe((const char*)src, (char*)dst,
                                        (int)src_len, (int)dst_cap);
            return n < 0 ? -1 : n;
        }
        case CODEC_ZLIB: {
            uLongf out_len = (uLongf)dst_cap;
            int rc = uncompress((Bytef*)dst, &out_len, (const Bytef*)src,
                                (uLong)src_len);
            return rc == Z_OK ? (int64_t)out_len : -1;
        }
    }
    return -1;
}

int64_t ct_compress(int32_t codec, const uint8_t* src, int64_t src_len,
                    uint8_t* dst, int64_t dst_cap, int32_t level) {
    switch (codec) {
        case CODEC_NONE:
            if (src_len > dst_cap) return -1;
            memcpy(dst, src, (size_t)src_len);
            return src_len;
        case CODEC_ZSTD: {
            size_t n = ZSTD_compress(dst, (size_t)dst_cap, src, (size_t)src_len,
                                     level);
            if (ZSTD_isError(n)) return -1;
            return (int64_t)n;
        }
        case CODEC_LZ4: {
            int n = LZ4_compress_default((const char*)src, (char*)dst,
                                         (int)src_len, (int)dst_cap);
            return n <= 0 ? -1 : n;
        }
        case CODEC_ZLIB: {
            uLongf out_len = (uLongf)dst_cap;
            int rc = compress2((Bytef*)dst, &out_len, (const Bytef*)src,
                               (uLong)src_len, level > 9 ? 9 : level);
            return rc == Z_OK ? (int64_t)out_len : -1;
        }
    }
    return -1;
}

int64_t ct_compress_bound(int32_t codec, int64_t src_len) {
    switch (codec) {
        case CODEC_NONE: return src_len;
        case CODEC_ZSTD: return (int64_t)ZSTD_compressBound((size_t)src_len);
        case CODEC_LZ4:  return (int64_t)LZ4_compressBound((int)src_len);
        case CODEC_ZLIB: return (int64_t)compressBound((uLong)src_len);
    }
    return -1;
}

// ---- batched stripe-chunk reads ----------------------------------------
// Reads n streams from one open file and decompresses each into its slot
// of a caller-provided contiguous output buffer.  This is the native
// inner loop of the stripe reader (one call per (stripe, column) scan).
// returns 0 on success, -(1+i) identifying the failing stream.

int64_t ct_read_streams(const char* path, int32_t codec, int64_t n,
                        const int64_t* offsets, const int64_t* comp_lens,
                        const int64_t* raw_lens, const int64_t* dst_offsets,
                        uint8_t* dst, int64_t dst_cap,
                        uint8_t* scratch, int64_t scratch_cap) {
    FILE* f = fopen(path, "rb");
    if (!f) return -1000000;
    for (int64_t i = 0; i < n; i++) {
        if (comp_lens[i] > scratch_cap) { fclose(f); return -(1 + i); }
        if (dst_offsets[i] + raw_lens[i] > dst_cap) { fclose(f); return -(1 + i); }
        if (fseeko(f, (off_t)offsets[i], SEEK_SET) != 0) { fclose(f); return -(1 + i); }
        if (fread(scratch, 1, (size_t)comp_lens[i], f) != (size_t)comp_lens[i]) {
            fclose(f);
            return -(1 + i);
        }
        int64_t got = ct_decompress(codec, scratch, comp_lens[i],
                                    dst + dst_offsets[i], raw_lens[i]);
        if (got != raw_lens[i]) { fclose(f); return -(1 + i); }
    }
    fclose(f);
    return 0;
}

// ---- parallel batched reads --------------------------------------------
// Same contract as ct_read_streams, but streams are claimed from a
// shared counter by a small thread pool; each worker owns a file handle
// and scratch buffer.  The reference parallelizes scans across worker
// backends; within one host process this is the analog for saturating
// storage + decompression bandwidth on cold scans.

int64_t ct_read_streams_mt(const char* path, int32_t codec, int64_t n,
                           const int64_t* offsets, const int64_t* comp_lens,
                           const int64_t* raw_lens, const int64_t* dst_offsets,
                           uint8_t* dst, int64_t dst_cap, int32_t n_threads) {
    std::atomic<int64_t> err{0};
    std::atomic<int64_t> next{0};
    auto worker = [&]() {
        FILE* f = fopen(path, "rb");
        if (!f) {
            int64_t expect = 0;
            err.compare_exchange_strong(expect, -1000000);
            return;
        }
        std::vector<uint8_t> scratch;
        while (err.load(std::memory_order_relaxed) == 0) {
            int64_t i = next.fetch_add(1);
            if (i >= n) break;
            int64_t fail = -(1 + i), expect = 0;
            if ((int64_t)scratch.size() < comp_lens[i]) {
                scratch.resize((size_t)comp_lens[i]);
            }
            if (dst_offsets[i] + raw_lens[i] > dst_cap ||
                fseeko(f, (off_t)offsets[i], SEEK_SET) != 0 ||
                fread(scratch.data(), 1, (size_t)comp_lens[i], f)
                    != (size_t)comp_lens[i] ||
                ct_decompress(codec, scratch.data(), comp_lens[i],
                              dst + dst_offsets[i], raw_lens[i]) != raw_lens[i]) {
                err.compare_exchange_strong(expect, fail);
                break;
            }
        }
        fclose(f);
    };
    int nt = n_threads < 1 ? 1 : (n_threads > 16 ? 16 : n_threads);
    if ((int64_t)nt > n) nt = (int)n;
    std::vector<std::thread> threads;
    for (int t = 0; t < nt; t++) threads.emplace_back(worker);
    for (auto& t : threads) t.join();
    return err.load();
}

// ---- one call per scan batch -------------------------------------------
// The value streams of a whole batch — about 2,000 streams out of some 32
// stripe files for 4 M rows — each decoded straight to its place in the
// batch's column arrays: stream i lies in file file_idx[i] and lands at
// byte dst_offs[i] of buffer dst_col[i] (dst_ptrs / dst_caps describe
// the buffers, so every write is bounds-checked here).  Files are opened
// once and read with pread, which threads may share; each worker owns
// its scratch and its zstd context.  A codec "none" stream is read where
// it belongs, with no scratch at all.
// returns 0 on success, -(1+i) identifying the failing stream,
// -1000000 - f for a file that does not open.
//
// ``stats`` (nullable, DECODE_STATS doubles) is what a tracing caller
// asks the pool about itself: [0] read_ms and [1] decompress_ms, thread
// time summed over the workers (a codec "none" stream is all read);
// [2] busy_max_ms, the slowest worker's time from its first claim to
// its exit; [3] the workers that ran.  With NULL no clock is read.

enum { DECODE_STATS = 4 };

int64_t ct_decode_batch(int32_t n_files, const char* const* paths,
                        const int32_t* file_codecs, int64_t n,
                        const int32_t* file_idx, const int64_t* offsets,
                        const int64_t* comp_lens, const int64_t* raw_lens,
                        const int32_t* dst_col, const int64_t* dst_offs,
                        int32_t n_dst, uint8_t* const* dst_ptrs,
                        const int64_t* dst_caps, int32_t n_threads,
                        double* stats) {
    using clk = std::chrono::steady_clock;
    const bool timed = stats != nullptr;
    struct WorkerStats { double read_ms = 0, decompress_ms = 0, busy_ms = 0; };
    auto ms_since = [](clk::time_point t0) {
        return std::chrono::duration<double, std::milli>(clk::now() - t0).count();
    };
    std::vector<int> fds((size_t)(n_files > 0 ? n_files : 0), -1);
    auto close_all = [&]() {
        for (int fd : fds) if (fd >= 0) close(fd);
    };
    for (int32_t f = 0; f < n_files; f++) {
        fds[f] = open(paths[f], O_RDONLY | O_CLOEXEC);
        if (fds[f] < 0) { close_all(); return -1000000 - f; }
    }
    std::atomic<int64_t> err{0};
    std::atomic<int64_t> next{0};
    auto read_all = [](int fd, uint8_t* buf, int64_t len, int64_t off) {
        while (len > 0) {
            ssize_t got = pread(fd, buf, (size_t)len, (off_t)off);
            if (got <= 0) return false;
            buf += got; off += got; len -= got;
        }
        return true;
    };
    auto worker = [&](WorkerStats* ws) {
        std::vector<uint8_t> scratch;
        ZSTD_DCtx* dctx = nullptr;
        clk::time_point w0, t0;
        if (timed) w0 = clk::now();
        while (err.load(std::memory_order_relaxed) == 0) {
            int64_t i = next.fetch_add(1);
            if (i >= n) break;
            int32_t f = file_idx[i], c = dst_col[i];
            bool ok = f >= 0 && f < n_files && c >= 0 && c < n_dst &&
                      comp_lens[i] >= 0 && raw_lens[i] >= 0 &&
                      dst_offs[i] >= 0 &&
                      dst_offs[i] + raw_lens[i] <= dst_caps[c];
            if (ok) {
                uint8_t* dst = dst_ptrs[c] + dst_offs[i];
                int32_t codec = file_codecs[f];
                if (timed) t0 = clk::now();
                if (codec == CODEC_NONE) {
                    ok = comp_lens[i] == raw_lens[i] &&
                         read_all(fds[f], dst, raw_lens[i], offsets[i]);
                    if (timed) ws->read_ms += ms_since(t0);
                } else {
                    if ((int64_t)scratch.size() < comp_lens[i])
                        scratch.resize((size_t)comp_lens[i]);
                    ok = read_all(fds[f], scratch.data(), comp_lens[i],
                                  offsets[i]);
                    if (timed) { ws->read_ms += ms_since(t0); t0 = clk::now(); }
                    if (ok && codec == CODEC_ZSTD) {
                        if (!dctx) dctx = ZSTD_createDCtx();
                        size_t got = dctx ? ZSTD_decompressDCtx(
                            dctx, dst, (size_t)raw_lens[i], scratch.data(),
                            (size_t)comp_lens[i]) : (size_t)-1;
                        ok = !ZSTD_isError(got) && (int64_t)got == raw_lens[i];
                    } else if (ok) {
                        ok = ct_decompress(codec, scratch.data(), comp_lens[i],
                                           dst, raw_lens[i]) == raw_lens[i];
                    }
                    if (timed) ws->decompress_ms += ms_since(t0);
                }
            }
            if (!ok) {
                int64_t expect = 0;
                err.compare_exchange_strong(expect, -(1 + i));
                break;
            }
        }
        if (dctx) ZSTD_freeDCtx(dctx);
        if (timed) ws->busy_ms = ms_since(w0);
    };
    int nt = n_threads < 1 ? 1 : (n_threads > 16 ? 16 : n_threads);
    if ((int64_t)nt > n) nt = (int)n;
    if (nt < 1) nt = 1;
    std::vector<WorkerStats> per_worker((size_t)nt);
    if (nt <= 1) {
        worker(&per_worker[0]);
    } else {
        std::vector<std::thread> threads;
        for (int t = 0; t < nt; t++) threads.emplace_back(worker, &per_worker[t]);
        for (auto& t : threads) t.join();
    }
    close_all();
    if (timed) {
        for (int k = 0; k < DECODE_STATS; k++) stats[k] = 0.0;
        for (const WorkerStats& ws : per_worker) {
            stats[0] += ws.read_ms;
            stats[1] += ws.decompress_ms;
            if (ws.busy_ms > stats[2]) stats[2] = ws.busy_ms;
        }
        stats[3] = (double)nt;
    }
    return err.load();
}

// ---- validity bitmap unpack --------------------------------------------
// big-endian bit order, matching numpy packbits

void ct_unpack_bits(const uint8_t* src, int64_t n_bits, uint8_t* dst) {
    for (int64_t i = 0; i < n_bits; i++) {
        dst[i] = (src[i >> 3] >> (7 - (i & 7))) & 1;
    }
}

int32_t ct_version(void) { return 1; }

}  // extern "C"
