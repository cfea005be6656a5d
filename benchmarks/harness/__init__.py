"""The benchmark's yardstick: traffic generation, the reduction from traces
and spans to metrics, the table of peaks, the work-counting functions, the
plain references and the comparison that decides ``correct``. Nothing here
is imported by the program; later PRs may add files beside these and may
not edit them."""
