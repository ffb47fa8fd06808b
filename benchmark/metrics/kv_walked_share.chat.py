"""Kernels: the share of the layer's page region that the block tables
named, summed over the window's decode steps and attention layers
(``serving.kv.pages_walked`` over ``serving.kv.pages_region``), in percent
(chat-knee80: at four fifths of the knee a handful of the 32 slots hold a
sequence of some hundreds of tokens; the rest of the region is never read).
Set by the traffic: it says how much of the region a step HAD to read, not
how well."""
from benchmark.readers_kv import walked_share as read  # noqa: F401
