"""Names by which the device trace shows the program's kernels (op names
contain one of these)."""

CAM_KERNEL = ("bacam_paged_topk_stage1",)
