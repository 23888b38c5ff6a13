"""Layer device_link: bytes put on the device (the program's
DeviceStats.h2d_bytes) per input file byte, over the window's scans."""
from ..harness import link_bytes_per_input_byte


def read(record: dict):
    return link_bytes_per_input_byte(record, "h2d_bytes")
