# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: prediction.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()


from google.protobuf import struct_pb2 as google_dot_protobuf_dot_struct__pb2


DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x10prediction.proto\x12\x11seldon_tpu.protos\x1a\x1cgoogle/protobuf/struct.proto\"S\n\x0b\x44\x65nseTensor\x12\'\n\x05\x64type\x18\x01 \x01(\x0e\x32\x18.seldon_tpu.protos.DType\x12\r\n\x05shape\x18\x02 \x03(\x03\x12\x0c\n\x04\x64\x61ta\x18\x03 \x01(\x0c\"/\n\x06Tensor\x12\x11\n\x05shape\x18\x01 \x03(\x05\x42\x02\x10\x01\x12\x12\n\x06values\x18\x02 \x03(\x01\x42\x02\x10\x01\"\xb7\x01\n\x0b\x44\x65\x66\x61ultData\x12\r\n\x05names\x18\x01 \x03(\t\x12+\n\x06tensor\x18\x02 \x01(\x0b\x32\x19.seldon_tpu.protos.TensorH\x00\x12-\n\x07ndarray\x18\x03 \x01(\x0b\x32\x1a.google.protobuf.ListValueH\x00\x12/\n\x05\x64\x65nse\x18\x04 \x01(\x0b\x32\x1e.seldon_tpu.protos.DenseTensorH\x00\x42\x0c\n\ndata_oneof\"\xe9\x01\n\x06Metric\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\x32\n\x04type\x18\x02 \x01(\x0e\x32$.seldon_tpu.protos.Metric.MetricType\x12\r\n\x05value\x18\x03 \x01(\x02\x12\x31\n\x04tags\x18\x04 \x03(\x0b\x32#.seldon_tpu.protos.Metric.TagsEntry\x1a+\n\tTagsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"/\n\nMetricType\x12\x0b\n\x07\x43OUNTER\x10\x00\x12\t\n\x05GAUGE\x10\x01\x12\t\n\x05TIMER\x10\x02\"\x90\x03\n\x04Meta\x12\x0c\n\x04puid\x18\x01 \x01(\t\x12/\n\x04tags\x18\x02 \x03(\x0b\x32!.seldon_tpu.protos.Meta.TagsEntry\x12\x35\n\x07routing\x18\x03 \x03(\x0b\x32$.seldon_tpu.protos.Meta.RoutingEntry\x12=\n\x0brequestPath\x18\x04 \x03(\x0b\x32(.seldon_tpu.protos.Meta.RequestPathEntry\x12*\n\x07metrics\x18\x05 \x03(\x0b\x32\x19.seldon_tpu.protos.Metric\x1a\x43\n\tTagsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12%\n\x05value\x18\x02 \x01(\x0b\x32\x16.google.protobuf.Value:\x02\x38\x01\x1a.\n\x0cRoutingEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\x05:\x02\x38\x01\x1a\x32\n\x10RequestPathEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"\x92\x01\n\x06Status\x12\x0c\n\x04\x63ode\x18\x01 \x01(\x05\x12\x0c\n\x04info\x18\x02 \x01(\t\x12\x0e\n\x06reason\x18\x03 \x01(\t\x12\x34\n\x06status\x18\x04 \x01(\x0e\x32$.seldon_tpu.protos.Status.StatusFlag\"&\n\nStatusFlag\x12\x0b\n\x07SUCCESS\x10\x00\x12\x0b\n\x07\x46\x41ILURE\x10\x01\"\xf1\x01\n\rSeldonMessage\x12)\n\x06status\x18\x01 \x01(\x0b\x32\x19.seldon_tpu.protos.Status\x12%\n\x04meta\x18\x02 \x01(\x0b\x32\x17.seldon_tpu.protos.Meta\x12.\n\x04\x64\x61ta\x18\x03 \x01(\x0b\x32\x1e.seldon_tpu.protos.DefaultDataH\x00\x12\x11\n\x07\x62inData\x18\x04 \x01(\x0cH\x00\x12\x11\n\x07strData\x18\x05 \x01(\tH\x00\x12*\n\x08jsonData\x18\x06 \x01(\x0b\x32\x16.google.protobuf.ValueH\x00\x42\x0c\n\ndata_oneof\"M\n\x11SeldonMessageList\x12\x38\n\x0eseldonMessages\x18\x01 \x03(\x0b\x32 .seldon_tpu.protos.SeldonMessage\"\xb2\x01\n\x08\x46\x65\x65\x64\x62\x61\x63k\x12\x31\n\x07request\x18\x01 \x01(\x0b\x32 .seldon_tpu.protos.SeldonMessage\x12\x32\n\x08response\x18\x02 \x01(\x0b\x32 .seldon_tpu.protos.SeldonMessage\x12\x0e\n\x06reward\x18\x03 \x01(\x02\x12/\n\x05truth\x18\x04 \x01(\x0b\x32 .seldon_tpu.protos.SeldonMessage\"3\n\nBatchIndex\x12\r\n\x05puids\x18\x01 \x03(\t\x12\x16\n\nrow_counts\x18\x02 \x03(\x05\x42\x02\x10\x01\"\xdb\x01\n\x0fGenerateRequest\x12%\n\x04meta\x18\x01 \x01(\x0b\x32\x17.seldon_tpu.protos.Meta\x12\x0e\n\x06prompt\x18\x02 \x01(\t\x12\x1c\n\x10prompt_token_ids\x18\x03 \x03(\x05\x42\x02\x10\x01\x12\x16\n\x0emax_new_tokens\x18\x04 \x01(\x05\x12\x13\n\x0btemperature\x18\x05 \x01(\x02\x12\r\n\x05top_p\x18\x06 \x01(\x02\x12\r\n\x05top_k\x18\x07 \x01(\x05\x12\x0c\n\x04seed\x18\x08 \x01(\x04\x12\x1a\n\x0estop_token_ids\x18\t \x03(\x05\x42\x02\x10\x01\"\xd1\x02\n\x10GenerateResponse\x12)\n\x06status\x18\x01 \x01(\x0b\x32\x19.seldon_tpu.protos.Status\x12%\n\x04meta\x18\x02 \x01(\x0b\x32\x17.seldon_tpu.protos.Meta\x12\x0c\n\x04text\x18\x03 \x01(\t\x12\x15\n\ttoken_ids\x18\x04 \x03(\x05\x42\x02\x10\x01\x12\x0f\n\x07ttft_ms\x18\x05 \x01(\x02\x12\x10\n\x08total_ms\x18\x06 \x01(\x02\x12\x15\n\rprompt_tokens\x18\x07 \x01(\x05\x12\x19\n\x11\x63ompletion_tokens\x18\x08 \x01(\x05\x12\x41\n\x07timings\x18\t \x03(\x0b\x32\x30.seldon_tpu.protos.GenerateResponse.TimingsEntry\x1a.\n\x0cTimingsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\x01:\x02\x38\x01*\xd7\x01\n\x05\x44Type\x12\x0e\n\nDT_INVALID\x10\x00\x12\x0e\n\nDT_FLOAT32\x10\x01\x12\x0e\n\nDT_FLOAT64\x10\x02\x12\x0f\n\x0b\x44T_BFLOAT16\x10\x03\x12\x0e\n\nDT_FLOAT16\x10\x04\x12\x0b\n\x07\x44T_INT8\x10\x05\x12\x0c\n\x08\x44T_INT16\x10\x06\x12\x0c\n\x08\x44T_INT32\x10\x07\x12\x0c\n\x08\x44T_INT64\x10\x08\x12\x0c\n\x08\x44T_UINT8\x10\t\x12\r\n\tDT_UINT16\x10\n\x12\r\n\tDT_UINT32\x10\x0b\x12\r\n\tDT_UINT64\x10\x0c\x12\x0b\n\x07\x44T_BOOL\x10\rb\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'prediction_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _TENSOR.fields_by_name['shape']._options = None
  _TENSOR.fields_by_name['shape']._serialized_options = b'\020\001'
  _TENSOR.fields_by_name['values']._options = None
  _TENSOR.fields_by_name['values']._serialized_options = b'\020\001'
  _METRIC_TAGSENTRY._options = None
  _METRIC_TAGSENTRY._serialized_options = b'8\001'
  _META_TAGSENTRY._options = None
  _META_TAGSENTRY._serialized_options = b'8\001'
  _META_ROUTINGENTRY._options = None
  _META_ROUTINGENTRY._serialized_options = b'8\001'
  _META_REQUESTPATHENTRY._options = None
  _META_REQUESTPATHENTRY._serialized_options = b'8\001'
  _BATCHINDEX.fields_by_name['row_counts']._options = None
  _BATCHINDEX.fields_by_name['row_counts']._serialized_options = b'\020\001'
  _GENERATEREQUEST.fields_by_name['prompt_token_ids']._options = None
  _GENERATEREQUEST.fields_by_name['prompt_token_ids']._serialized_options = b'\020\001'
  _GENERATEREQUEST.fields_by_name['stop_token_ids']._options = None
  _GENERATEREQUEST.fields_by_name['stop_token_ids']._serialized_options = b'\020\001'
  _GENERATERESPONSE_TIMINGSENTRY._options = None
  _GENERATERESPONSE_TIMINGSENTRY._serialized_options = b'8\001'
  _GENERATERESPONSE.fields_by_name['token_ids']._options = None
  _GENERATERESPONSE.fields_by_name['token_ids']._serialized_options = b'\020\001'
  _DTYPE._serialized_start=2297
  _DTYPE._serialized_end=2512
  _DENSETENSOR._serialized_start=69
  _DENSETENSOR._serialized_end=152
  _TENSOR._serialized_start=154
  _TENSOR._serialized_end=201
  _DEFAULTDATA._serialized_start=204
  _DEFAULTDATA._serialized_end=387
  _METRIC._serialized_start=390
  _METRIC._serialized_end=623
  _METRIC_TAGSENTRY._serialized_start=531
  _METRIC_TAGSENTRY._serialized_end=574
  _METRIC_METRICTYPE._serialized_start=576
  _METRIC_METRICTYPE._serialized_end=623
  _META._serialized_start=626
  _META._serialized_end=1026
  _META_TAGSENTRY._serialized_start=859
  _META_TAGSENTRY._serialized_end=926
  _META_ROUTINGENTRY._serialized_start=928
  _META_ROUTINGENTRY._serialized_end=974
  _META_REQUESTPATHENTRY._serialized_start=976
  _META_REQUESTPATHENTRY._serialized_end=1026
  _STATUS._serialized_start=1029
  _STATUS._serialized_end=1175
  _STATUS_STATUSFLAG._serialized_start=1137
  _STATUS_STATUSFLAG._serialized_end=1175
  _SELDONMESSAGE._serialized_start=1178
  _SELDONMESSAGE._serialized_end=1419
  _SELDONMESSAGELIST._serialized_start=1421
  _SELDONMESSAGELIST._serialized_end=1498
  _FEEDBACK._serialized_start=1501
  _FEEDBACK._serialized_end=1679
  _BATCHINDEX._serialized_start=1681
  _BATCHINDEX._serialized_end=1732
  _GENERATEREQUEST._serialized_start=1735
  _GENERATEREQUEST._serialized_end=1954
  _GENERATERESPONSE._serialized_start=1957
  _GENERATERESPONSE._serialized_end=2294
  _GENERATERESPONSE_TIMINGSENTRY._serialized_start=2248
  _GENERATERESPONSE_TIMINGSENTRY._serialized_end=2294
# @@protoc_insertion_point(module_scope)
