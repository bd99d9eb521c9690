SELECT i_item_id, i_item_desc, s_state,
       COUNT(ss_quantity) AS store_sales_quantitycount,
       AVG(ss_quantity) AS store_sales_quantityave,
       STDDEV_SAMP(ss_quantity) AS store_sales_quantitystdev,
       COUNT(sr_return_quantity) AS store_returns_quantitycount,
       AVG(sr_return_quantity) AS store_returns_quantityave,
       STDDEV_SAMP(sr_return_quantity) AS store_returns_quantitystdev,
       COUNT(cs_quantity) AS catalog_sales_quantitycount,
       AVG(cs_quantity) AS catalog_sales_quantityave,
       STDDEV_SAMP(cs_quantity) AS catalog_sales_quantitystdev
FROM store_sales, store_returns, catalog_sales,
     (SELECT d_date_sk AS d1_date_sk, d_quarter_name AS d1_quarter_name
      FROM date_dim) d1,
     (SELECT d_date_sk AS d2_date_sk, d_quarter_name AS d2_quarter_name
      FROM date_dim) d2,
     (SELECT d_date_sk AS d3_date_sk, d_quarter_name AS d3_quarter_name
      FROM date_dim) d3,
     store, item
WHERE d1_quarter_name = '2000Q1' AND d1_date_sk = ss_sold_date_sk
  AND i_item_sk = ss_item_sk AND s_store_sk = ss_store_sk
  AND ss_customer_sk = sr_customer_sk AND ss_item_sk = sr_item_sk
  AND ss_ticket_number = sr_ticket_number
  AND sr_returned_date_sk = d2_date_sk
  AND d2_quarter_name IN ('2000Q1', '2000Q2', '2000Q3')
  AND sr_customer_sk = cs_bill_customer_sk AND sr_item_sk = cs_item_sk
  AND cs_sold_date_sk = d3_date_sk
  AND d3_quarter_name IN ('2000Q1', '2000Q2', '2000Q3')
GROUP BY i_item_id, i_item_desc, s_state
ORDER BY i_item_id, i_item_desc, s_state
LIMIT 100
