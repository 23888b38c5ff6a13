"""Layer device_link: bytes brought back from the device (the program's
DeviceStats.d2h_bytes) per input file byte, over the window's scans."""
from ..harness import link_bytes_per_input_byte


def read(record: dict):
    return link_bytes_per_input_byte(record, "d2h_bytes")
