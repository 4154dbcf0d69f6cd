"""
Seeded digit-like inputs in the shape of ``mnist8m`` (infimnist: MNIST
deformed): a dense float32 matrix of 28 x 28 images, raw pixel values
in [0, 1], NOT standardised. Beside ``datagen.py`` (which may not
change): a configuration whose driver is ``search_pixels`` names a
generator of THIS module.
"""

import numpy as np

SIDE = 28
#: pixels of the 28 x 28 frame that are zero in every row (the corners
#: and the outer border: MNIST's training set has some 65 of them)
ZERO_PIXELS = 65


def _frame():
    """Which pixels are always zero: the ``ZERO_PIXELS`` farthest from
    the centre."""
    r, c = np.divmod(np.arange(SIDE * SIDE), SIDE)
    far = np.hypot(r - (SIDE - 1) / 2, c - (SIDE - 1) / 2)
    always_zero = np.zeros(SIDE * SIDE, bool)
    always_zero[np.argsort(-far, kind="stable")[:ZERO_PIXELS]] = True
    return always_zero


def _blur(img):
    """One pass of a 3 x 3 binomial blur (what a pen's width and the
    anti-aliasing of MNIST's rescaling do to a stroke)."""
    p = np.pad(img, 1)
    rows = p[:-2] + 2 * p[1:-1] + p[2:]
    return (rows[:, :-2] + 2 * rows[:, 1:-1] + rows[:, 2:]) / 16.0


def _prototype(bits, strokes, steps):
    """One hand of one class: a few pen strokes, each a short random
    walk with momentum inside the 20 x 20 box, blurred to a pen's
    width, the brightest pixel at 1."""
    img = np.zeros((SIDE, SIDE), np.float32)
    for _ in range(strokes):
        pos = bits.uniform(6, 22, 2)
        step = bits.normal(0, 1, 2)
        for _ in range(steps):
            step = 0.8 * step + 0.6 * bits.normal(0, 1, 2)
            step /= max(1.0, np.hypot(*step))
            pos = np.clip(pos + step, 4.5, 22.5)
            img[int(pos[0]), int(pos[1])] = 1.0
    img = _blur(_blur(img * 4.0))
    return np.minimum(img / img.max() * 1.6, 1.0)


def digit_like_rows(seed, n, d=SIDE * SIDE, k=10, hands=3, shift=2,
                    strokes=5, steps=15, noise=0.35, ink_floor=0.2,
                    strays=3, label_noise=0.07, block=32768, threads=8):
    """``(X, y)``: ``X`` an ``(n, 784)`` float32 matrix of digit-like
    images, ``y`` one of ``k`` balanced classes.

    Every class has ``hands`` prototypes (pen strokes in the 20 x 20
    box). A row is a blend of two hands of its class, moved by up to
    ``shift`` pixels each way (infimnist's deformations are small
    translations and elastic warps of MNIST's digits), its ink scaled
    and disturbed pixel by pixel by ``noise``; what stays under
    ``ink_floor`` is paper: exactly 0, as some four pixels in five of a
    MNIST row are; ``strays`` specks of ink fall anywhere inside the
    frame. Pixels are clipped to [0, 1] and never standardised,
    so columns differ in scale by orders of magnitude and the
    ``ZERO_PIXELS`` of the frame are zero in every row — raw pixels, on
    which L-BFGS runs to sklearn's iteration cap. ``label_noise`` of
    the labels are redrawn uniformly; with the overlap of the classes'
    strokes a multinomial linear model reaches about 0.92, as it does on
    MNIST. Made in blocks of rows, each from a stream of its own
    spawned from the seed, by a few threads: the same seed gives the
    same data whatever the threads do."""
    from concurrent.futures import ThreadPoolExecutor

    if d != SIDE * SIDE:
        raise ValueError("digit_like_rows makes 28 x 28 images")
    streams = np.random.SeedSequence(int(seed) % (2 ** 32)).spawn(
        1 + -(-n // block))
    first = np.random.Generator(np.random.SFC64(streams[0]))
    always_zero = _frame()
    inside = np.flatnonzero(~always_zero)
    shifts = [(a, b) for a in range(-shift, shift + 1)
              for b in range(-shift, shift + 1)]
    protos = np.stack([
        np.stack([_prototype(first, strokes, steps) for _ in range(hands)])
        for _ in range(k)])
    # every (class, hand, shift) once: (k, hands, shifts, 784)
    moved = np.stack([
        np.roll(protos, s, axis=(2, 3)).reshape(k, hands, -1)
        for s in shifts], axis=2)
    moved[..., always_zero] = 0.0
    y = (first.permutation(n) % k).astype(np.int64)
    X = np.empty((n, d), dtype=np.float32)

    def fill(i):
        bits = np.random.Generator(np.random.SFC64(streams[1 + i]))
        rows = X[i * block:(i + 1) * block]
        cls = y[i * block:(i + 1) * block]
        m = len(rows)
        a, b = bits.integers(0, hands, (2, m))
        s, t = bits.integers(0, len(shifts), (2, m))
        mix = bits.random(m, dtype=np.float32)[:, None]
        bits.standard_normal(out=rows, dtype=np.float32)
        ink = mix * moved[cls, a, s] + (1 - mix) * moved[cls, b, t]
        gain = 0.7 + 0.6 * bits.random(m, dtype=np.float32)[:, None]
        rows *= noise
        rows += gain
        rows *= ink
        rows[rows < ink_floor] = 0.0
        np.minimum(rows, 1.0, out=rows)
        rows[np.arange(m)[:, None],
             inside[bits.integers(0, len(inside), (m, strays))]] = (
                 bits.random((m, strays), dtype=np.float32))
        redraw = bits.random(m) < label_noise
        cls[redraw] = bits.integers(0, k, int(redraw.sum()))

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(len(streams) - 1)))
    return X, y


GENERATORS = {"digit_like_rows": digit_like_rows}


def make(data, seed):
    """``datagen.make`` over this module's generators: the group's own
    ``seed`` where it pins one (``datagen.make`` says why a grid search
    pins its data), else the run's; ``k`` rides along for the driver
    and is the generator's too."""
    kwargs = {k: v for k, v in data.items() if k not in ("generator", "seed")}
    return GENERATORS[data["generator"]](data.get("seed", seed), **kwargs)
