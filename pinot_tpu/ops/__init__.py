"""Device execution backend: jit'd JAX (XLA) kernels for the query hot path.

This is the TPU-native rewrite of pinot-core's per-segment operator chain
(SURVEY.md §3.2): instead of BlockDocIdSet iterators + per-block
DataFetcher reads + scalar aggregation loops, whole columns are staged in
HBM as [num_segments, padded_docs] int32 dictId blocks and one jit'd
kernel per (query-shape, schema) computes filter masks, gathers dictionary
values, and reduces — batched across segments on the mesh's `segments`
axis (the DP analog of CombinePlanNode fan-out,
combine/BaseCombineOperator.java:54).
"""
