"""Image encryption with the chaotic-oscillator PRNG — the paper's
motivating application (§I: countering attacks on image encryption needs a
high-throughput PRNG).

Encrypt a synthetic image by XOR with the chaotic keystream; verify
(a) exact decryption, (b) ciphertext histogram flatness (chi-square),
(c) adjacent-pixel correlation collapse — the standard chaotic-crypto checks.

Run:  PYTHONPATH=src python examples/chaotic_encryption.py
"""
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

from repro.prng import default_stream


def make_test_image(n=128):
    """Smooth synthetic image (high adjacent-pixel correlation)."""
    y, x = np.mgrid[0:n, 0:n]
    img = (128 + 60 * np.sin(x / 9.0) * np.cos(y / 13.0)
           + 40 * np.exp(-((x - 64) ** 2 + (y - 64) ** 2) / 800.0))
    return img.astype(np.uint8)


def adjacent_correlation(img):
    a = img[:, :-1].astype(np.float64).ravel()
    b = img[:, 1:].astype(np.float64).ravel()
    return float(np.corrcoef(a, b)[0, 1])


def main():
    img = make_test_image()
    n_bytes = img.size
    print(f"plaintext: {img.shape}, adjacent-pixel corr = "
          f"{adjacent_correlation(img):.4f}")

    stream = default_stream(n_streams=256, seed=7)
    words = np.asarray(stream.bits((n_bytes + 3) // 4))
    keystream = words.view(np.uint8)[:n_bytes].reshape(img.shape)

    cipher = img ^ keystream
    print(f"ciphertext: adjacent-pixel corr = "
          f"{adjacent_correlation(cipher):.4f}")

    # histogram flatness: chi-square over 256 bins
    hist, _ = np.histogram(cipher, bins=256, range=(0, 256))
    expected = n_bytes / 256
    chi2 = float(((hist - expected) ** 2 / expected).sum())
    # 99% critical value for 255 dof ~ 310.5
    print(f"ciphertext histogram chi2 = {chi2:.1f} "
          f"({'flat (<310.5)' if chi2 < 310.5 else 'NOT flat'})")

    # decryption (stream is counter-based: regenerate the same keystream)
    stream2 = default_stream(n_streams=256, seed=7)
    words2 = np.asarray(stream2.bits((n_bytes + 3) // 4))
    keystream2 = words2.view(np.uint8)[:n_bytes].reshape(img.shape)
    recovered = cipher ^ keystream2
    ok = np.array_equal(recovered, img)
    print(f"decryption exact: {ok}")
    assert ok and abs(adjacent_correlation(cipher)) < 0.05 and chi2 < 310.5
    print("encryption demo complete.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
