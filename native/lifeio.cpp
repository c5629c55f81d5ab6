// Native runtime for mpi_and_open_mp_tpu: config parsing and two serial
// Life oracles.
//
// The reference's cfg loader is compiled C (3-life/life2d.c:52-72); this
// framework keeps it native as well, next to the oracles the device
// kernels are checked against. Exposed as a plain C ABI for ctypes
// (mpi_and_open_mp_tpu/utils/native.py). Built fresh for this project —
// one buffered read instead of the reference's fscanf per token.
//
// Build: make -C native     (produces liblifeio.so)

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

using std::uint8_t;

extern "C" {

// Parse a .cfg file: header[5] = {steps, save_steps, nx, ny, ncells};
// *cells_out = malloc'd flat (i, j) pairs (2*ncells int64), owned by the
// caller via lifeio_free. Returns 0 on success, negative error codes
// otherwise (-1 open, -2 header, -3 dangling coordinate).
int lifeio_load_config(const char *path, long long header[5],
                       long long **cells_out) {
    *cells_out = nullptr;
    FILE *fd = std::fopen(path, "rb");
    if (!fd) return -1;

    std::fseek(fd, 0, SEEK_END);
    long size = std::ftell(fd);
    std::fseek(fd, 0, SEEK_SET);
    std::string text(static_cast<size_t>(size), '\0');
    size_t got = std::fread(text.data(), 1, static_cast<size_t>(size), fd);
    std::fclose(fd);
    text.resize(got);

    std::vector<long long> tokens;
    const char *s = text.c_str();
    char *end = nullptr;
    while (*s) {
        while (*s == ' ' || *s == '\t' || *s == '\n' || *s == '\r') ++s;
        if (!*s) break;
        long long v = std::strtoll(s, &end, 10);
        if (end == s) return -2;  // non-numeric garbage
        tokens.push_back(v);
        s = end;
    }
    if (tokens.size() < 4) return -2;
    size_t ncoords = tokens.size() - 4;
    if (ncoords % 2) return -3;

    for (int k = 0; k < 4; ++k) header[k] = tokens[k];
    long long ncells = static_cast<long long>(ncoords / 2);
    header[4] = ncells;
    if (ncells) {
        auto *cells = static_cast<long long *>(
            std::malloc(sizeof(long long) * ncoords));
        if (!cells) return -4;
        std::memcpy(cells, tokens.data() + 4, sizeof(long long) * ncoords);
        *cells_out = cells;
    }
    return 0;
}

void lifeio_free(long long *p) { std::free(p); }

// Serial Game-of-Life oracle: advance a (ny, nx) uint8 board `steps`
// generations on a periodic torus. Same role as the reference's compiled
// life2d oracle (/root/reference/3-life/life2d.c:104-130): an independent,
// native ground truth the JAX/Pallas kernels are checked against — written
// here as a scanline pass with explicit wrap rows/columns rather than the
// reference's per-cell modular ind() arithmetic.
void lifeio_life_steps(uint8_t *board, long long nx, long long ny,
                       long long steps) {
    std::vector<uint8_t> next(static_cast<size_t>(nx * ny));
    for (long long s = 0; s < steps; ++s) {
        for (long long j = 0; j < ny; ++j) {
            const uint8_t *up = board + ((j - 1 + ny) % ny) * nx;
            const uint8_t *mid = board + j * nx;
            const uint8_t *dn = board + ((j + 1) % ny) * nx;
            uint8_t *out = next.data() + j * nx;
            for (long long i = 0; i < nx; ++i) {
                long long il = (i - 1 + nx) % nx, ir = (i + 1) % nx;
                int n = up[il] + up[i] + up[ir] + mid[il] + mid[ir] +
                        dn[il] + dn[i] + dn[ir];
                out[i] = (n == 3 || (n == 2 && mid[i])) ? 1 : 0;
            }
        }
        std::memcpy(board, next.data(), static_cast<size_t>(nx * ny));
    }
}

namespace {

// out[i] = v[(i-1+nx) % nx] over a 64-cells/word packed row.
void shift_toward_higher(const std::uint64_t *v, std::uint64_t *out,
                         long long W, long long nx, std::uint64_t last_mask) {
    for (long long w = 0; w < W; ++w)
        out[w] = (v[w] << 1) | (w ? (v[w - 1] >> 63) : 0);
    out[0] |= (v[W - 1] >> ((nx - 1) & 63)) & 1ULL;  // torus wrap
    out[W - 1] &= last_mask;
}

// out[i] = v[(i+1) % nx].
void shift_toward_lower(const std::uint64_t *v, std::uint64_t *out,
                        long long W, long long nx, std::uint64_t last_mask) {
    for (long long w = 0; w < W; ++w)
        out[w] = (v[w] >> 1) | (w + 1 < W ? (v[w + 1] << 63) : 0);
    out[W - 1] &= last_mask;
    out[W - 1] |= (v[0] & 1ULL) << ((nx - 1) & 63);  // torus wrap
}

}  // namespace

// Bit-packed serial oracle: 64 cells per uint64 along x, carry-save-adder
// rule — the host twin of the TPU kernels' bitwise algorithm
// (mpi_and_open_mp_tpu/ops/bitlife.py), ~50x the scalar oracle above on
// big boards. Kept as a SECOND independent native implementation; tests
// cross-check it against both the scalar path and the NumPy oracle.
void lifeio_life_steps_bits(uint8_t *board, long long nx, long long ny,
                            long long steps) {
    const long long W = (nx + 63) / 64;
    const std::uint64_t last_mask =
        (nx % 64) ? ((1ULL << (nx % 64)) - 1) : ~0ULL;
    std::vector<std::uint64_t> cur(static_cast<size_t>(W * ny), 0);
    std::vector<std::uint64_t> nxt(static_cast<size_t>(W * ny), 0);
    for (long long j = 0; j < ny; ++j)
        for (long long i = 0; i < nx; ++i)
            if (board[j * nx + i])
                cur[j * W + i / 64] |= 1ULL << (i % 64);

    std::vector<std::uint64_t> v0(W), v1(W), l0(W), r0(W), l1(W), r1(W);
    for (long long s = 0; s < steps; ++s) {
        for (long long j = 0; j < ny; ++j) {
            const std::uint64_t *up = &cur[((j - 1 + ny) % ny) * W];
            const std::uint64_t *mid = &cur[j * W];
            const std::uint64_t *dn = &cur[((j + 1) % ny) * W];
            for (long long w = 0; w < W; ++w) {
                std::uint64_t a = up[w], b = mid[w], c = dn[w];
                v0[w] = a ^ b ^ c;                  // vertical triple sum,
                v1[w] = (a & b) | (c & (a ^ b));    // 2-bit carry-save
            }
            shift_toward_higher(v0.data(), l0.data(), W, nx, last_mask);
            shift_toward_lower(v0.data(), r0.data(), W, nx, last_mask);
            shift_toward_higher(v1.data(), l1.data(), W, nx, last_mask);
            shift_toward_lower(v1.data(), r1.data(), W, nx, last_mask);
            std::uint64_t *out = &nxt[j * W];
            for (long long w = 0; w < W; ++w) {
                std::uint64_t t0 = l0[w] ^ v0[w] ^ r0[w];
                std::uint64_t k0 =
                    (l0[w] & v0[w]) | (r0[w] & (l0[w] ^ v0[w]));
                std::uint64_t u0 = l1[w] ^ v1[w] ^ r1[w];
                std::uint64_t u1 =
                    (l1[w] & v1[w]) | (r1[w] & (l1[w] ^ v1[w]));
                std::uint64_t t1 = u0 ^ k0;
                std::uint64_t vc = u0 & k0;
                std::uint64_t t2 = u1 ^ vc;
                std::uint64_t t3 = u1 & vc;
                // alive' = T==3 | (alive & T==4), T includes the centre.
                std::uint64_t is3 = t0 & t1 & ~t2 & ~t3;
                std::uint64_t is4 = ~t0 & ~t1 & t2 & ~t3;
                out[w] = (is3 | (mid[w] & is4)) &
                         (w == W - 1 ? last_mask : ~0ULL);
            }
        }
        cur.swap(nxt);
    }
    for (long long j = 0; j < ny; ++j)
        for (long long i = 0; i < nx; ++i)
            board[j * nx + i] =
                static_cast<uint8_t>((cur[j * W + i / 64] >> (i % 64)) & 1);
}

}  // extern "C"
