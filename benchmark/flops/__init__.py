"""FLOPs and bytes as functions of shapes, one module per model family or
kernel, found by the name a configuration or a metric file gives."""
