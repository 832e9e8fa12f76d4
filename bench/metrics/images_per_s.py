"""Images served ok by the window's steps over the window's seconds
(the window ends with its last step)."""


def read(rec):
    return rec.images_ok / rec.window_s if rec.images_ok else None
