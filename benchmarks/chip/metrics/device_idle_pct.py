"""Device: the share of the window in which no operation ran on the chip,
averaged over the chips used."""


def read(layers):
    if layers.device is None:
        return None
    busy = layers.device.busy_s()
    if not busy:
        return None
    return 100.0 * (1.0 - busy / layers.device.window_s)
