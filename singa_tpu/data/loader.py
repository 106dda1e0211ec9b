"""Dataset -> shard converter CLI (the reference's build/loader).

Mirrors tools/data_loader/ semantics: shards are opened in append mode so a
crashed run resumes where it stopped (data_loader.cc:12-14,122), and MNIST
idx files are parsed with the same big-endian magic/meta layout
(data_source.cc:25-95). Keys are zero-padded record indices.

Sources:
  mnist      train/test idx file pairs -> pixel-bytes records (shape 28x28)
  cifar      CIFAR-10 binary batches (data_batch_*.bin / test_batch.bin,
             1 label byte + 3072 RGB bytes per record) -> (3,32,32) records
  imagenet   ImageNet-layout folder (img/ + rid.txt label list) -> RGB
             (3,size,size) records via PIL resize, the reference's
             ImageNetSource (data_source.cc:97-196)
  digits     sklearn load_digits upscaled to 28x28 — a real, learnable
             stand-in when the MNIST files aren't on disk (this image has no
             network egress); accuracy-parity tests train on this
  synthetic  deterministic Gaussian-blob classes (grayscale or RGB via
             --channels), for benchmarks/smoke tests

Interop: ``shard2lmdb`` / ``lmdb2shard`` convert to/from Caffe-style LMDB
databases (singa_tpu/data/lmdbio.py) for kLMDBData configs.

Mean files: ``compute-mean`` writes a per-pixel mean.npy over a shard, the
counterpart of the reference's binaryproto image mean
(data_source.cc:129-137); rgbimage_param.meanfile points at it.

Usage:
  python -m singa_tpu.data.loader mnist  --image-file f --label-file f --output DIR
  python -m singa_tpu.data.loader cifar  --bin-files f1 f2 ... --output DIR
  python -m singa_tpu.data.loader digits --output DIR [--split train|test]
  python -m singa_tpu.data.loader synthetic --output DIR --n 1000 [--classes 10] [--channels 3]
  python -m singa_tpu.data.loader imagenet --folder DIR --output DIR [--size 256]
  python -m singa_tpu.data.loader compute-mean --input DIR --output mean.npy
  python -m singa_tpu.data.loader split --input DIR --prefix P --n N [--mode equal|head]
  python -m singa_tpu.data.loader shard2lmdb --input DIR --output DIR
  python -m singa_tpu.data.loader lmdb2shard --input DIR --output DIR
"""

from __future__ import annotations

import argparse
import os
import struct
import sys

import numpy as np

from .records import ImageRecord, encode_record
from .shard import ShardReader, ShardWriter


def _key(i: int) -> str:
    return f"{i:08d}"


def write_records(
    folder: str, images: np.ndarray, labels: np.ndarray, append: bool = True
) -> int:
    """Write uint8 (N,H,W) images + labels as Records; returns #inserted.

    Fresh shards encode through the native C++ codec when built
    (byte-identical output, singa_tpu/native); appends go through the
    Python writer because its key set deduplicates against existing
    records, matching the reference loader's resume semantics.
    """
    images = np.asarray(images, dtype=np.uint8)
    from .. import native
    from .shard import shard_path

    os.makedirs(folder, exist_ok=True)
    if not (append and os.path.exists(shard_path(folder))):
        fast = native.write_records(shard_path(folder), images, labels)
        if fast is not None:
            return fast
    n = 0
    with ShardWriter(folder, append=append) as w:
        for i, (img, label) in enumerate(zip(images, labels)):
            rec = ImageRecord(
                shape=list(img.shape), label=int(label), pixel=img.tobytes()
            )
            if w.insert(_key(i), encode_record(rec)):
                n += 1
        w.flush()
    return n


# ---------------------------- sources ----------------------------


def read_idx_images(path: str) -> np.ndarray:
    """Parse an MNIST idx3-ubyte image file (data_source.cc:31-54)."""
    with open(path, "rb") as f:
        magic, num, h, w = struct.unpack(">IIII", f.read(16))
        if magic != 2051:
            raise ValueError(f"{path}: bad image magic {magic} (want 2051)")
        buf = f.read(num * h * w)
    return np.frombuffer(buf, dtype=np.uint8).reshape(num, h, w)


def read_idx_labels(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        magic, num = struct.unpack(">II", f.read(8))
        if magic != 2049:
            raise ValueError(f"{path}: bad label magic {magic} (want 2049)")
        buf = f.read(num)
    return np.frombuffer(buf, dtype=np.uint8)


def read_cifar_bins(paths: list[str]) -> tuple[np.ndarray, np.ndarray]:
    """Parse CIFAR-10 binary batch files: each record is 1 label byte
    followed by 3072 bytes (3 channels x 32x32, channel-major — already
    the (C,H,W) layout our RGB records use)."""
    rec = 1 + 3 * 32 * 32
    images, labels = [], []
    for path in paths:
        buf = np.fromfile(path, dtype=np.uint8)
        if buf.size % rec:
            raise ValueError(
                f"{path}: size {buf.size} is not a multiple of {rec}"
            )
        rows = buf.reshape(-1, rec)
        labels.append(rows[:, 0])
        images.append(rows[:, 1:].reshape(-1, 3, 32, 32))
    return np.concatenate(images), np.concatenate(labels)


def compute_mean(folder: str, out_path: str) -> np.ndarray:
    """Per-pixel float32 mean over every record in a shard, saved as .npy
    (the reference's mean binaryproto, data_source.cc:129-137)."""
    from .pipeline import load_shard_arrays

    images, _ = load_shard_arrays(folder)
    mean = images.astype(np.float64).mean(axis=0).astype(np.float32)
    np.save(out_path, mean)
    return mean


def digits_arrays(split: str = "train") -> tuple[np.ndarray, np.ndarray]:
    """sklearn 8x8 digits, nearest-upscaled to 28x28 uint8 images."""
    from sklearn.datasets import load_digits

    d = load_digits()
    images = (d.images / d.images.max() * 255.0).astype(np.uint8)
    # 8x8 -> 32x32 via kron, center-crop to 28x28
    big = np.kron(images, np.ones((1, 4, 4), dtype=np.uint8))
    big = big[:, 2:30, 2:30]
    labels = d.target.astype(np.uint8)
    # deterministic 80/20 split, interleaved so class balance holds
    test_mask = np.arange(len(big)) % 5 == 4
    if split == "test":
        return big[test_mask], labels[test_mask]
    return big[~test_mask], labels[~test_mask]


def synthetic_arrays(
    n: int,
    classes: int = 10,
    size: int = 28,
    seed: int = 0,
    noise_seed: int | None = None,
    channels: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Gaussian class-template blobs: learnable, deterministic, no IO.

    ``seed`` fixes the class templates, ``noise_seed`` the per-sample noise —
    pass different noise seeds to get disjoint train/test splits of the same
    classification problem. ``channels`` > 0 makes (C,H,W) RGB-style
    records (CIFAR-shaped with channels=3, size=32).
    """
    rng = np.random.RandomState(seed)
    shape = (channels, size, size) if channels else (size, size)
    templates = rng.rand(classes, *shape) * 160.0
    labels = (np.arange(n) % classes).astype(np.uint8)
    nrng = rng if noise_seed is None else np.random.RandomState(noise_seed)
    noise = nrng.rand(n, *shape) * 95.0
    images = (templates[labels] + noise).clip(0, 255).astype(np.uint8)
    return images, labels


def structured_rgb(
    n: int,
    classes: int = 10,
    seed: int = 0,
    noise_seed: int | None = None,
    class_amplitude: float | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Spatially-structured synthetic RGB: kron-upsampled 8x8 class
    templates (CIFAR-shaped 3x32x32). Weight-shared convs cannot
    discriminate the iid-noise templates of synthetic_arrays (each pixel
    independent), so conv-net convergence runs need low-frequency class
    structure. ``noise_seed`` works like synthetic_arrays'.

    ``class_amplitude`` (r5) controls class overlap: None keeps the
    legacy fully-independent templates (amplitude 160, trivially
    separable — fine for short smoke oracles but the 70k-step AlexNet
    run saturates at 100%, a ceiling-pinned metric that cannot detect a
    regression). A float A builds templates as shared_base + U(0, A)
    per-class delta against the U(0, 95) pixel noise, so the task has a
    real Bayes error: pairwise template separation is A*sqrt(3072/6) ~
    22.6*A against sample noise sigma 27.4 along the discriminant —
    A ~ 6 targets ~90% optimal accuracy for 10 classes."""
    rng = np.random.RandomState(seed)
    if class_amplitude is None:
        small = rng.rand(classes, 3, 8, 8) * 160
    else:
        a = float(class_amplitude)
        base = rng.rand(1, 3, 8, 8) * (160.0 - a)
        small = base + rng.rand(classes, 3, 8, 8) * a
    templates = np.kron(small, np.ones((1, 1, 4, 4)))
    labels = (np.arange(n) % classes).astype(np.uint8)
    nrng = rng if noise_seed is None else np.random.RandomState(noise_seed)
    noise = nrng.rand(n, 3, 32, 32) * 95
    return (templates[labels] + noise).clip(0, 255).astype(np.uint8), labels


def load_label_lines(path: str) -> list[tuple[str, int]]:
    """Parse an ImageNet rid.txt label list: whitespace-separated
    "relative/img/path label" pairs (data_source.cc:109-127)."""
    with open(path) as f:
        toks = f.read().split()
    if len(toks) % 2:
        raise ValueError(f"{path}: odd token count (path without label)")
    return [(toks[i], int(toks[i + 1])) for i in range(0, len(toks), 2)]


def imagenet_records(folder: str, size: int):
    """Stream (key, ImageRecord) pairs from an ImageNet-layout folder:
    ``folder/img/`` + ``folder/rid.txt`` (data_source.cc:97-196).

    Images decode through PIL (the reference uses OpenCV), resize to
    size x size, and store raw channel-major RGB uint8. Two deliberate
    divergences from the reference, both documented here: channel order is
    RGB (not OpenCV's BGR — consistent within this framework's RGB
    pipeline), and the image mean is NOT subtracted at load time (the
    reference quantizes mean-subtracted floats back into bytes,
    data_source.cc:163-173, losing precision; here RGBImageLayer subtracts
    the float meanfile inside the jitted step instead)."""
    from PIL import Image

    lines = load_label_lines(os.path.join(folder, "rid.txt"))
    img_dir = os.path.join(folder, "img")
    for relpath, label in lines:
        path = os.path.join(img_dir, relpath)
        try:
            with Image.open(path) as im:
                im = im.convert("RGB")
                if size > 0:
                    im = im.resize((size, size), Image.BILINEAR)
                arr = np.asarray(im, dtype=np.uint8)
        except OSError as e:
            print(f"skipping invalid img {path}: {e}", file=sys.stderr)
            continue
        chw = np.ascontiguousarray(arr.transpose(2, 0, 1))  # (3,H,W)
        yield relpath, ImageRecord(
            shape=list(chw.shape), label=label, pixel=chw.tobytes()
        )


def write_imagenet(folder: str, output: str, size: int) -> int:
    """ImageNet folder -> shard, record-streamed (never holds the dataset
    in memory); append mode resumes a crashed conversion by key like the
    reference loader (data_loader.cc:12-14,122)."""
    n = 0
    shapes: set[tuple[int, ...]] = set()
    with ShardWriter(output, append=True) as w:
        for key, rec in imagenet_records(folder, size):
            shapes.add(tuple(rec.shape))
            if w.insert(key, encode_record(rec)):
                n += 1
        w.flush()
    if len(shapes) > 1:
        print(
            f"WARNING: {output} holds {len(shapes)} distinct image shapes "
            "(--size 0 with mixed-size inputs); such a shard cannot be "
            "batched at training time — rerun with --size N",
            file=sys.stderr,
        )
    return n


# ---------------------------- split (reference Split/SplitN) -----------


def split_shard(input_dir: str, prefix: str, n: int, mode: str = "equal"):
    with ShardReader(input_dir) as reader:
        tuples = list(reader)
    total = len(tuples)
    if mode == "equal":
        if n >= total:
            raise ValueError("too many sub-shards")
        sizes = [total // n + (total % n if i == 0 else 0) for i in range(n)]
        pos = 0
        for i, sz in enumerate(sizes):
            with ShardWriter(f"{prefix}-{i}", append=True) as w:
                for k, v in tuples[pos : pos + sz]:
                    w.insert(k, v)
                w.flush()
            pos += sz
    else:  # head: first n records into -0, rest into -1
        if n >= total:
            raise ValueError("sub shard must be smaller than original")
        for i, chunk in enumerate((tuples[:n], tuples[n:])):
            with ShardWriter(f"{prefix}-{i}", append=True) as w:
                for k, v in chunk:
                    w.insert(k, v)
                w.flush()


def text_token_arrays(
    path: str, seq_len: int, stride: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Byte-level LM dataset from any text/binary file: overlapping
    fixed-length windows of raw bytes (vocab 256). Labels are unused (the
    kLMLoss target is the sequence itself)."""
    with open(path, "rb") as f:
        raw = np.frombuffer(f.read(), dtype=np.uint8)
    if len(raw) < seq_len + 1:
        raise ValueError(f"{path}: shorter than one {seq_len}-byte window")
    stride = stride or seq_len
    # inclusive stop: the window starting at len-seq_len is valid (kLMLoss
    # targets are within-window)
    starts = np.arange(0, len(raw) - seq_len + 1, stride)
    tokens = np.stack([raw[s : s + seq_len] for s in starts])
    return tokens, np.zeros(len(tokens), dtype=np.uint8)


def synthetic_token_arrays(
    n: int, seq_len: int = 128, vocab: int = 64, seed: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Learnable synthetic sequences: a fixed random Markov chain over
    ``vocab`` symbols (deterministic given seed) — next-token accuracy
    well above chance is reachable, so LM convergence tests mean
    something."""
    if not 2 <= vocab <= 256:
        raise ValueError(
            f"vocab must be in [2, 256] (uint8 token records), got {vocab}"
        )
    rng = np.random.RandomState(seed)
    # each symbol strongly prefers one successor (80%), else uniform
    succ = rng.randint(0, vocab, size=vocab)
    seqs = np.empty((n, seq_len), dtype=np.uint8)
    state = rng.randint(0, vocab, size=n)
    for t in range(seq_len):
        seqs[:, t] = state
        follow = rng.rand(n) < 0.8
        state = np.where(follow, succ[state], rng.randint(0, vocab, size=n))
    return seqs, np.zeros(n, dtype=np.uint8)


# ------------------- LMDB interop (reference kLMDBData) -------------------


def shard_to_lmdb(input_dir: str, output_dir: str) -> int:
    """Re-encode a shard as a Caffe-style LMDB of Datum messages, keyed
    like Caffe's convert tools (%08d). Lets kLMDBData configs run against
    data produced by this loader."""
    from .lmdbio import LMDBError, write_lmdb
    from .records import Datum, decode_record, encode_datum

    def datums():
        with ShardReader(input_dir) as reader:
            for key, val in reader:
                rec = decode_record(val)
                shape = list(rec.shape) + [1] * (3 - len(rec.shape))
                if len(rec.shape) == 2:  # (H,W) grayscale -> C=1
                    shape = [1, rec.shape[0], rec.shape[1]]
                d = Datum(
                    channels=shape[0], height=shape[1], width=shape[2],
                    data=rec.pixel, label=rec.label, float_data=rec.data,
                )
                # latin-1 mirrors lmdb_to_shard's decode: keys are raw bytes
                yield (key.encode("latin-1") if isinstance(key, str)
                       else key, encode_datum(d))

    try:
        # loader-written shards insert zero-padded ascending keys, so the
        # streaming O(page)-memory path normally wins
        return write_lmdb(output_dir, datums(), assume_sorted=True)
    except LMDBError as e:
        if "out of order" not in str(e):
            raise
        return write_lmdb(output_dir, datums())


def lmdb_to_shard(input_dir: str, output_dir: str) -> int:
    """Convert a Caffe LMDB into a shard (the migration path the old
    kLMDBData error message promised)."""
    from .lmdbio import LMDBReader
    from .records import datum_to_image_record, decode_datum, encode_record

    n = 0
    with LMDBReader(input_dir) as reader, ShardWriter(
        output_dir, append=True
    ) as w:
        for key, val in reader:
            rec = datum_to_image_record(decode_datum(val))
            if w.insert(key.decode("latin-1"), encode_record(rec)):
                n += 1
        w.flush()
    return n


# ---------------------------- CLI ----------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="singa_tpu.data.loader")
    sub = ap.add_subparsers(dest="source", required=True)

    p = sub.add_parser("mnist")
    p.add_argument("--image-file", required=True)
    p.add_argument("--label-file", required=True)
    p.add_argument("--output", required=True)

    p = sub.add_parser("cifar")
    p.add_argument("--bin-files", nargs="+", required=True)
    p.add_argument("--output", required=True)

    p = sub.add_parser("digits")
    p.add_argument("--output", required=True)
    p.add_argument("--split", choices=("train", "test"), default="train")

    p = sub.add_parser("synthetic")
    p.add_argument("--output", required=True)
    p.add_argument("--n", type=int, default=1000)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--size", type=int, default=28)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--channels", type=int, default=0)

    p = sub.add_parser("text")
    p.add_argument("--input", required=True, help="any text/binary file")
    p.add_argument("--output", required=True)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--stride", type=int, default=0,
                   help="window stride (default seq-len, non-overlapping)")

    p = sub.add_parser("tokens")
    p.add_argument("--output", required=True)
    p.add_argument("--n", type=int, default=1000)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)

    p = sub.add_parser("imagenet")
    p.add_argument("--folder", required=True,
                   help="dataset root holding img/ and rid.txt")
    p.add_argument("--output", required=True)
    p.add_argument("--size", type=int, default=256,
                   help="resize to size x size, squashing aspect ratio "
                   "like the reference loader (0 = keep original sizes; "
                   "only batchable if every image already matches)")

    p = sub.add_parser("compute-mean")
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)

    p = sub.add_parser("shard2lmdb")
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)

    p = sub.add_parser("lmdb2shard")
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)

    p = sub.add_parser("split")
    p.add_argument("--input", required=True)
    p.add_argument("--prefix", required=True)
    p.add_argument("--n", type=int, required=True)
    p.add_argument("--mode", choices=("equal", "head"), default="equal")

    args = ap.parse_args(argv)
    if args.source == "mnist":
        images = read_idx_images(args.image_file)
        labels = read_idx_labels(args.label_file)
        if len(images) != len(labels):
            raise ValueError("image/label count mismatch")
        n = write_records(args.output, images, labels)
    elif args.source == "cifar":
        n = write_records(args.output, *read_cifar_bins(args.bin_files))
    elif args.source == "digits":
        n = write_records(args.output, *digits_arrays(args.split))
    elif args.source == "synthetic":
        n = write_records(
            args.output,
            *synthetic_arrays(
                args.n, args.classes, args.size, args.seed,
                channels=args.channels,
            ),
        )
    elif args.source == "text":
        n = write_records(
            args.output, *text_token_arrays(args.input, args.seq_len,
                                            args.stride)
        )
    elif args.source == "tokens":
        n = write_records(
            args.output,
            *synthetic_token_arrays(args.n, args.seq_len, args.vocab,
                                    args.seed),
        )
    elif args.source == "imagenet":
        n = write_imagenet(args.folder, args.output, args.size)
    elif args.source == "shard2lmdb":
        n = shard_to_lmdb(args.input, args.output)
        print(f"wrote {n} datums into {os.path.join(args.output, 'data.mdb')}")
        return 0
    elif args.source == "lmdb2shard":
        n = lmdb_to_shard(args.input, args.output)
    elif args.source == "compute-mean":
        mean = compute_mean(args.input, args.output)
        print(f"mean {tuple(mean.shape)} -> {args.output}")
        return 0
    else:
        split_shard(args.input, args.prefix, args.n, args.mode)
        print(f"split {args.input} -> {args.prefix}-*")
        return 0
    print(f"inserted {n} records into {os.path.join(args.output, 'shard.dat')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
