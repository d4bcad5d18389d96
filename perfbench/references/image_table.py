"""Plain reference of the table's decode and transform: JPEG bytes to the
float32 NHWC image the train step is fed (shorter side to 256 with
bilinear resampling where it is not 256 already, centre crop, scale to
[0, 1], normalise with the ImageNet mean and deviation).  PIL only."""

import io

import numpy as np

MEAN = np.array([0.485, 0.456, 0.406], np.float32)
STD = np.array([0.229, 0.224, 0.225], np.float32)


def decode(jpeg: bytes, crop: int, resize: int = 256) -> np.ndarray:
    from PIL import Image

    img = Image.open(io.BytesIO(jpeg)).convert("RGB")
    w, h = img.size
    if min(w, h) != resize:
        scale = resize / min(w, h)
        img = img.resize((max(1, round(w * scale)), max(1, round(h * scale))),
                         Image.BILINEAR)
        w, h = img.size
    left, top = (w - crop) // 2, (h - crop) // 2
    arr = np.asarray(img.crop((left, top, left + crop, top + crop)),
                     np.float32) / 255.0
    return (arr - MEAN) / STD
